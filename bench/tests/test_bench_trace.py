"""The trace reduction, on a small hand-made trace with known intervals
(``data/trace_small.pbtxt``; times below in ns).

Window [1000, 11000]. Program runs: jit_something [500, 1500] (clipped
to [1000, 1500]), jit__collect_frontier_stacked [2100, 3000],
jit_leaf_topk_l2_raw [3500, 4000], jit_leaf_topk_l2 [6500, 7500]: busy
2900 of 10000. Idle gaps: [1500, 2100] and [4000, 6500] fall outside
any program span (3100); [3000, 3500] and [7500, 11000] have their
middles inside an ``engine.dispatch`` span (4000). The Pallas calls are
the custom-calls: two under the scope ``kernel.leaf_topk_l2``, 300 and
800 long, and one with no scope, 200 long."""
import os

import pytest

from bench import readers, trace_reduce, work

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "trace_small.pbtxt")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    with open(FIXTURE) as f:
        return trace_reduce.reduce_space(ProfileData.from_text_proto(f.read()))


def test_busy_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(10000e-9)
    assert reduced["busy_s"] == pytest.approx(2900e-9)


def test_device_ops_by_program(reduced):
    assert dict(reduced["device_ops"]) == pytest.approx({
        "jit_leaf_topk_l2": 1000e-9, "jit__collect_frontier_stacked": 900e-9,
        "jit_something": 500e-9, "jit_leaf_topk_l2_raw": 500e-9})


def test_idle_gaps_by_host_span(reduced):
    assert dict(reduced["idle_gaps"]) == pytest.approx({
        trace_reduce.NO_SPAN: 3100e-9, "engine.dispatch": 4000e-9})


def test_kernel_calls_and_shapes(reduced):
    k = reduced["kernels"]
    assert sorted(k) == ["custom-call", "kernel.leaf_topk_l2"]
    raw, f32 = k["kernel.leaf_topk_l2"]
    assert raw["seconds"] == pytest.approx(300e-9)
    assert raw["shapes"] == [
        ("f32", [64, 16]), ("s32", [64, 16]), ("s32", [64, 16]),
        ("f32", [64, 128]), ("bf16", [64, 256, 128]), ("s32", [64, 256]),
        ("f32", [64, 1])]
    assert f32["seconds"] == pytest.approx(800e-9)
    assert ("f32", [64, 512, 128]) in f32["shapes"]
    assert k["custom-call"][0]["seconds"] == pytest.approx(200e-9)


def test_roofline_from_logical_shapes(reduced):
    ctx = type("Ctx", (), {})()
    ctx.trace = reduced
    ctx.device = {"kind": "TPU v5 lite"}
    ctx.cell = type("Cell", (), {"config": {"dim": 3}})()
    peak = work.peaks("TPU v5 lite")
    least = (work.least_seconds(work.leaf_topk_l2(64, 256, 3, 2, 16), peak)[0]
             + work.least_seconds(work.leaf_topk_l2(64, 512, 3, 4, 8), peak)[0])
    got = readers.roofline_pct(ctx, "kernel.leaf_topk_l2")
    assert got == pytest.approx(100 * least / 1100e-9)
    assert readers.roofline_pct(ctx, "kernel.topk_l2") is None
    assert readers.idle_pct(ctx) == pytest.approx(71.0)
