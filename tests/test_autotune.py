"""Shape-aware block autotuner: plan validity, cache identity per
shape class, the REPRO_BLOCK_PLAN pin (incl. validation errors), and
the decisions() export schema."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import autotune as at
from repro.kernels import ops
from repro.kernels import topk_l2 as tk


@pytest.fixture(autouse=True)
def _fresh_cache(monkeypatch):
    monkeypatch.delenv("REPRO_BLOCK_PLAN", raising=False)
    at.reset()
    yield
    at.reset()


def test_choose_plan_feasible_and_ranked():
    plan = at.choose_plan("topk_l2", 512, 4096, 64, 8)
    assert plan["source"] == "analytic"
    assert plan["bm"] % 8 == 0
    assert plan["bn"] & (plan["bn"] - 1) == 0
    assert 2 * plan["vmem_bytes"] <= at.chip()["vmem_budget"]
    # the winner scores no worse than every other feasible candidate
    ranked = at._rank("topk_l2", 512, 4096, 64, 8)
    assert plan["score"] == ranked[0]["score"]
    assert all(plan["score"] <= p["score"] for p in ranked)


def test_cache_is_per_shape_class():
    """Shapes in one pow2 bucket share one cached decision object;
    a different bucket re-ranks."""
    a = at.choose_plan("topk_l2", 300, 3000, 48, 8)
    b = at.choose_plan("topk_l2", 400, 2100, 33, 8)  # same pow2 class
    assert a is b
    c = at.choose_plan("topk_l2", 800, 3000, 48, 8)  # different class
    assert c is not a


def test_env_pin_overrides_and_validates(monkeypatch):
    monkeypatch.setenv("REPRO_BLOCK_PLAN", "32x256")
    plan = at.choose_plan("topk_l2", 512, 4096, 64, 8)
    assert plan["source"] == "env"
    assert (plan["bm"], plan["bn"]) == (32, 256)
    assert plan["bk"] == min(512, 128)  # block_plan clamps bk to d-pad

    for bad in ("foo", "7x128", "32x100", "8x128x100", "0x128", "32"):
        with pytest.raises(ValueError):
            at.parse_block_plan_env(bad)
    assert at.parse_block_plan_env("8x128x256") == (8, 128, 256)
    assert at.parse_block_plan_env("16x512") == (16, 512, 512)


def test_ops_wrapper_uses_tuned_blocks_and_explicit_pins_win():
    """The ops wrapper resolves blocks through the autotuner (a cache
    entry appears) unless the caller pins any block size explicitly."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    p = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    g = jnp.arange(64, dtype=jnp.int32)
    d, i = ops.topk_l2(q, p, g, np.inf, 4)
    assert any(key[0] == "topk_l2" for key in at._CACHE)
    # explicit pin: same numerics, no new autotune decision for the pin
    n0 = len(at._CACHE)
    d2, i2 = ops.topk_l2(q, p, g, np.inf, 4, bm=8, bn=128, bk=128)
    assert len(at._CACHE) == n0
    assert np.array_equal(np.asarray(d), np.asarray(d2))
    assert np.array_equal(np.asarray(i), np.asarray(i2))


def test_measured_mode_prefers_wall_clock():
    """With a measure callback the winner carries measured_us and the
    fastest measured candidate wins."""
    times = {}

    def fake_measure(plan):
        # contrive: bigger bm "runs faster", inverting the analytic rank
        t = 1.0 / plan["bm"]
        times[(plan["bm"], plan["bn"], plan["bk"])] = t
        return t

    plan = at.choose_plan(
        "topk_l2", 512, 4096, 64, 8, measure=fake_measure, trials=3
    )
    assert plan["source"] == "measured"
    assert plan["measured_us"] == min(times.values()) * 1e6


def test_decisions_export_schema():
    at.choose_plan("topk_l2", 512, 4096, 64, 8)
    at.choose_plan("leaf_topk_l2", 64, 1024, 16, 8)
    dec = at.decisions()
    assert len(dec) == 2
    for key, plan in dec.items():
        kernel, cls, kk, dtype, kind = key.split("/")
        assert kernel in ("topk_l2", "leaf_topk_l2")
        assert kk.startswith("k")
        for field in ("bm", "bn", "bk", "blocks"):
            assert isinstance(plan[field], int) and plan[field] > 0
        for field in ("padded_flops", "stream_bytes", "vmem_bytes",
                      "pred_us"):
            assert plan[field] >= 0
        assert plan["source"] in ("env", "analytic", "measured")
        assert all(isinstance(x, int) for x in plan["grid"])


def test_block_plan_cost_terms_are_block_independent_vs_dependent():
    """`hbm_bytes` (the accounting term the obs tests pin) must not
    move with block choice; the ranking terms (`stream_bytes`,
    `vmem_bytes`) must respond to it. (`flops` moves only through bn's
    selection-stage count, so it is invariant at fixed bn.)"""
    a = tk.block_plan(512, 4096, 64, 8, bm=8, bn=128, bk=128)
    b = tk.block_plan(512, 4096, 64, 8, bm=128, bn=512, bk=512)
    c = tk.block_plan(512, 4096, 64, 8, bm=256, bn=128, bk=512)
    assert a["hbm_bytes"] == b["hbm_bytes"] == c["hbm_bytes"]
    assert a["flops"] == c["flops"]  # same bn: identical flop bill
    assert a["stream_bytes"] != b["stream_bytes"]
    assert a["vmem_bytes"] != b["vmem_bytes"]


def test_chip_table_is_keyed_by_device_kind():
    """Interpret mode on the CPU ranks for the v5e row by name; a TPU
    kind without a sourced row raises instead of borrowing peaks."""
    assert at.device_kind() == at.CPU_CHIP == "TPU v5 lite"
    assert at.chip() is at.CHIPS["TPU v5 lite"]
    with pytest.raises(ValueError, match="no envelope"):
        at.chip("TPU v4")
    with pytest.raises(ValueError, match="no envelope"):
        at.choose_plan("topk_l2", 512, 4096, 64, 8, kind="TPU v4")
    plan = at.choose_plan("topk_l2", 512, 4096, 64, 8, kind="TPU v5 lite")
    assert plan is at.choose_plan("topk_l2", 512, 4096, 64, 8)
