"""Observability layer: registry semantics (thread-safe exact counts,
mergeable histograms, reset-in-place), per-query traces whose paper
metrics match the host oracle bit-exactly, engine counter migration,
and the BENCH_obs.json round-trip + schema gate."""
import importlib.util
import json
import pathlib
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import TreeSpec
from repro.core import search_host as sh
from repro.index import StreamingConfig, StreamingIndex
from repro.query import QuerySpec
from repro.query import engine as qengine

SPEC = TreeSpec.ballstar(leaf_size=8)


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.REGISTRY.enable()
    obs.reset()
    yield
    obs.REGISTRY.enable()
    obs.reset()


# -- metrics registry ---------------------------------------------------------
def test_counter_gauge_histogram_basics():
    reg = obs.metrics.Registry()
    c = reg.counter("c", kind="x")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("c", kind="x") is c  # get-or-create identity
    g = reg.gauge("g")
    g.set(2.5)
    assert g.value == 2.5
    h = reg.histogram("h", unit="s")
    for v in (0.001, 0.002, 0.004, 1.0):
        h.observe(v)
    assert h.count == 4 and h.unit == "s"
    assert h.percentile(50) >= 0.002
    with pytest.raises(TypeError):
        reg.gauge("c", kind="x")  # same key, different kind


def test_disable_pauses_and_reset_keeps_handles():
    reg = obs.metrics.Registry()
    c = reg.counter("c")
    c.inc(3)
    reg.disable()
    c.inc(100)
    assert c.value == 3  # disabled: mutation is a no-op
    reg.enable()
    reg.reset()
    assert c.value == 0
    c.inc()  # the cached handle is still the registered metric
    assert reg.counter("c").value == 1


def test_histogram_buckets_merge_exactly():
    """The log2 ladder is process-global, so percentiles survive a
    merge of shards: merged percentile == percentile of the union."""
    rng = np.random.default_rng(0)
    reg = obs.metrics.Registry()
    a = reg.histogram("a", unit="s")
    b = reg.histogram("b", unit="s")
    va = rng.lognormal(sigma=3.0, size=500)
    vb = rng.lognormal(mean=2.0, sigma=2.0, size=300)
    for v in va:
        a.observe(v)
    for v in vb:
        b.observe(v)
    u = reg.histogram("u", unit="s")
    for v in np.concatenate([va, vb]):
        u.observe(v)
    a.merge_from(b)
    assert a.count == u.count == 800
    for p in (50, 90, 95, 99):
        assert a.percentile(p) == u.percentile(p)


def test_bucket_of_edges():
    b = obs.metrics.bucket_of
    lo = obs.metrics.LOG2_LO
    assert b(0.0) == 0 and b(-1.0) == 0
    assert b(float("inf")) == obs.metrics.N_BUCKETS - 1
    # exact powers of two land in the bucket whose UPPER edge they are
    for e in (-3, 0, 5):
        i = b(2.0 ** e)
        assert obs.metrics.bucket_upper(i) == 2.0 ** e
        assert b(2.0 ** e * 1.001) == i + 1
    assert b(2.0 ** (lo - 5)) == 0  # underflow clamps


def test_snapshot_key_format_and_roundtrip(tmp_path):
    reg = obs.metrics.Registry()
    reg.counter("engine.dispatches", kind="traversal").inc(7)
    reg.gauge("index.n_live", index="idx9").set(123)
    reg.histogram("span.serve.search", unit="s").observe(0.25)
    snap = reg.snapshot()
    assert snap["counters"]["engine.dispatches{kind=traversal}"] == 7
    assert snap["gauges"]["index.n_live{index=idx9}"] == 123.0
    h = snap["histograms"]["span.serve.search"]
    assert h["unit"] == "s" and h["count"] == 1
    path = obs.export.dump_json(str(tmp_path / "BENCH_obs.json"), reg)
    loaded = obs.export.load_json(path)
    assert loaded["section"] == "obs"
    assert loaded["obs"] == json.loads(json.dumps(snap))  # JSON-stable
    assert "span.serve.search" in obs.export.table(loaded["obs"])


def test_counter_thread_hammer_exact():
    """Raw registry counters never lose increments under contention."""
    reg = obs.metrics.Registry()
    c = reg.counter("hammer")
    n_threads, per = 8, 2000

    def work():
        for _ in range(per):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == n_threads * per


# -- engine migration + thread safety (satellite: the racing globals) --------
def _small_index(rng, dim=3, segments=2, delta=True):
    idx = StreamingIndex(
        StreamingConfig(dim=dim, delta_capacity=64, spec=SPEC)
    )
    for s in range(segments):
        # distinct sizes -> distinct shape classes is NOT required;
        # what matters is a stable segment set for the snapshot
        idx.bulk_load(rng.standard_normal((40 + 30 * s, dim)))
    if delta:
        idx.add(rng.standard_normal((10, dim)))
    return idx


def test_engine_dispatch_counts_exact_under_threads():
    """N threads querying concurrently: dispatch accounting stays
    exact. The pre-registry module globals (`_DISPATCHES += 1`) lost
    increments under exactly this load."""
    rng = np.random.default_rng(2)
    idx = _small_index(rng)
    snap = idx.snapshot()
    queries = rng.standard_normal((4, 3))
    spec = QuerySpec(k=3, radius=np.inf)
    qengine.execute(snap, queries, spec)  # warm the jit cache
    n_classes = len(qengine.plan(snap))
    assert n_classes >= 1 and snap.delta_n_live > 0
    per_call = n_classes + 1  # traversal dispatches + the delta kernel

    before = qengine.dispatch_count()
    n_threads, per = 6, 8
    errs = []

    def work():
        try:
            for _ in range(per):
                qengine.execute(snap, queries, spec)
        except Exception as e:  # pragma: no cover - failure reporting
            errs.append(e)

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    assert (
        qengine.dispatch_count() - before == n_threads * per * per_call
    )
    cs = qengine.compile_stats()
    assert cs["dispatches"] == qengine.dispatch_count()
    assert cs["traversal_dispatches"] >= n_threads * per * n_classes
    ss = qengine.stack_stats()
    assert ss["full_builds"] + ss["incremental_updates"] >= 1


# -- QueryTrace + paper-metric exactness (satellite 3) ------------------------
def _host_totals(idx, queries, k, r):
    """Per-query (visits, leaves, candidates) summed over the host
    oracle run on every segment tree + the exhaustive delta scan."""
    out = np.zeros((len(queries), 3), np.int64)
    for seg in idx.segments:
        if seg.n_live == 0:
            continue
        for i, q in enumerate(queries):
            st = sh.constrained_knn(seg.tree, q, k, r)
            out[i] += (
                st.nodes_visited,
                st.leaves_visited,
                st.points_examined,
            )
    out[:, 2] += idx.delta.n_live  # arena scan: every live slot evaluated
    return out


@pytest.mark.parametrize("n_segments,with_delta", [(3, False), (2, True)])
def test_paper_metrics_match_host_oracle(n_segments, with_delta):
    """Engine per-query nodes-visited / leaves-scanned / candidate
    counts == the host oracle, bit-exactly — including the stacked
    pow2 dummy-pad correction (3 same-class segments pad to 4)."""
    rng = np.random.default_rng(7)
    idx = StreamingIndex(
        StreamingConfig(dim=3, delta_capacity=64, spec=SPEC)
    )
    for _ in range(n_segments):
        # near-equal sizes so segments share a shape class and the
        # stacked batch carries a dummy pad member when n_segments=3
        idx.bulk_load(rng.standard_normal((50 + int(rng.integers(0, 8)), 3)))
    if with_delta:
        idx.add(rng.standard_normal((17, 3)))
    queries = rng.standard_normal((6, 3))
    k, r = 4, 1.5

    with obs.trace.QueryTrace() as qt:
        res = qengine.execute(
            idx.snapshot(), queries, QuerySpec(k=k, radius=r, return_visits=True)
        )
    want = _host_totals(idx, queries, k, r)
    np.testing.assert_array_equal(res.nodes_visited, want[:, 0])
    np.testing.assert_array_equal(res.leaves_scanned, want[:, 1])
    np.testing.assert_array_equal(res.points_examined, want[:, 2])
    # the trace saw the same numbers without return_visits plumbing
    np.testing.assert_array_equal(qt.metrics["nodes_visited"], want[:, 0])
    np.testing.assert_array_equal(qt.metrics["leaves_scanned"], want[:, 1])
    np.testing.assert_array_equal(
        qt.metrics["candidates_evaluated"], want[:, 2]
    )
    assert qt.metrics["n_live"] == idx.n_live
    assert qt.metrics["delta_candidates"] == (
        idx.delta.n_live if with_delta else 0
    )
    # stage spans cover the engine pipeline
    assert "engine.plan" in qt.stages and "engine.merge" in qt.stages
    assert "engine.dispatch" in qt.stages
    if with_delta:
        assert "engine.delta" in qt.stages
    s = qt.summary()
    assert s["metrics"]["nodes_visited"]["total"] == int(want[:, 0].sum())
    assert 0.0 <= s["pruned_fraction"] <= 1.0


def test_paper_metrics_delta_only_and_tombstoned():
    """Degenerate classes: arena-only (zero traversal, candidates ==
    n_live) and fully-tombstoned (all zeros, zero dispatches)."""
    rng = np.random.default_rng(9)
    idx = StreamingIndex(StreamingConfig(dim=2, delta_capacity=64, spec=SPEC))
    g = idx.add(rng.standard_normal((20, 2)))  # delta only, no seal
    queries = rng.standard_normal((3, 2))
    spec = QuerySpec(k=5, radius=np.inf, return_visits=True)

    res = qengine.execute(idx.snapshot(), queries, spec)
    np.testing.assert_array_equal(res.nodes_visited, 0)
    np.testing.assert_array_equal(res.leaves_scanned, 0)
    np.testing.assert_array_equal(res.points_examined, 20)

    idx.delete(g)  # everything tombstoned
    before = qengine.dispatch_count()
    with obs.trace.QueryTrace() as qt:
        res = qengine.execute(idx.snapshot(), queries, spec)
    assert qengine.dispatch_count() == before  # answered on the host
    assert (res.gids == -1).all()
    np.testing.assert_array_equal(res.nodes_visited, 0)
    np.testing.assert_array_equal(res.points_examined, 0)
    np.testing.assert_array_equal(qt.metrics["candidates_evaluated"], 0)
    assert qt.metrics["n_live"] == 0


def test_trace_without_return_visits_and_span_nesting():
    """QueryTrace alone (no return_visits) still collects metrics; the
    result stays lean (None fields). Nested traces restore the outer."""
    rng = np.random.default_rng(11)
    idx = _small_index(rng, segments=1, delta=False)
    queries = rng.standard_normal((2, 3))
    with obs.trace.QueryTrace() as outer:
        with obs.trace.QueryTrace() as inner:
            res = qengine.execute(
                idx.snapshot(), queries, QuerySpec(k=2, radius=1.0)
            )
        assert obs.trace.current_query_trace() is outer
    assert obs.trace.current_query_trace() is None
    assert res.nodes_visited is None and res.points_examined is None
    assert "nodes_visited" in inner.metrics
    assert "nodes_visited" not in outer.metrics
    # spans landed on the registry too
    h = obs.REGISTRY.find("span.engine.dispatch")
    assert h is not None and h.count >= 1 and h.unit == "s"


# -- instrumented write path / kernels / serving ------------------------------
def test_kernel_accounting_bills_calls():
    from repro.kernels import ops
    from repro.kernels import topk_l2 as tk

    q = np.random.default_rng(0).standard_normal((8, 4)).astype(np.float32)
    p = np.random.default_rng(1).standard_normal((32, 4)).astype(np.float32)
    g = np.arange(32, dtype=np.int32)
    import jax.numpy as jnp

    ops.topk_l2(jnp.asarray(q), jnp.asarray(p), jnp.asarray(g), np.inf, 3)
    c = obs.REGISTRY.find("kernel.calls", kernel="topk_l2")
    b = obs.REGISTRY.find("kernel.hbm_bytes", kernel="topk_l2")
    assert c is not None and c.value == 1
    plan = tk.block_plan(8, 32, 4, 3)
    assert b.value == plan["hbm_bytes"]
    # the plan mirrors the kernel's own clamps
    assert plan["kp"] == 4 and plan["grid"][2] >= 1


def test_bench_schema_checker(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "check_bench_schema",
        pathlib.Path(__file__).resolve().parents[1]
        / "benchmarks"
        / "check_bench_schema.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    good_section = {
        "section": "kernels",
        "records": [{"name": "a", "value": 1.0, "unit": "us_per_call"}],
    }
    (tmp_path / "BENCH_kernels.json").write_text(json.dumps(good_section))
    # a real registry snapshot is schema-valid by construction
    reg = obs.metrics.Registry()
    reg.counter("c").inc(2)
    reg.histogram("h", unit="s").observe(0.5)
    obs.export.dump_json(str(tmp_path / "BENCH_obs.json"), reg)
    assert mod.main(["prog", str(tmp_path)]) == 0

    # drop a required field -> nonzero exit
    bad = json.loads((tmp_path / "BENCH_obs.json").read_text())
    del bad["obs"]["histograms"]["h"]["unit"]
    (tmp_path / "BENCH_obs.json").write_text(json.dumps(bad))
    assert mod.main(["prog", str(tmp_path)]) == 1
    # missing obs artifact entirely -> nonzero exit
    (tmp_path / "BENCH_obs.json").unlink()
    assert mod.main(["prog", str(tmp_path)]) == 1
    # records missing unit -> nonzero exit
    obs.export.dump_json(str(tmp_path / "BENCH_obs.json"), reg)
    good_section["records"][0].pop("unit")
    (tmp_path / "BENCH_kernels.json").write_text(json.dumps(good_section))
    assert mod.main(["prog", str(tmp_path)]) == 1


def test_serve_spans_and_counters():
    from repro.serve.retrieval import Datastore

    rng = np.random.default_rng(5)
    keys = rng.standard_normal((60, 4)).astype(np.float32)
    store = Datastore.from_pairs(keys, np.zeros(60, np.int64), leaf_size=16)
    store.lookup(keys[:3], k=2, r=1.0)
    assert obs.REGISTRY.find("serve.queries").value == 3
    for name in ("span.serve.lookup", "span.serve.search"):
        h = obs.REGISTRY.find(name)
        assert h is not None and h.count == 1 and h.unit == "s"


def test_obs_snapshot_includes_engine_and_index_series():
    """End-to-end: one mixed workload populates every instrumented
    layer's series in a single snapshot()."""
    rng = np.random.default_rng(13)
    idx = _small_index(rng)
    idx.constrained_knn(rng.standard_normal((2, 3)), 3, 1.0)
    snap = obs.snapshot()
    assert snap["counters"]["engine.dispatches{kind=traversal}"] >= 1
    assert snap["counters"]["engine.dispatches{kind=delta}"] >= 1
    assert any(k.startswith("index.inserts") for k in snap["counters"])
    assert any(
        k.startswith("index.delta_occupancy") for k in snap["gauges"]
    )
    assert any(
        k.startswith("span.engine.dispatch") for k in snap["histograms"]
    )


# -- spans inside the engine dispatch, phase-2 fill, program builds -----------
FUSED_CHILD_SPANS = (
    "engine.phase1",
    "engine.frontier_sync",
    "engine.phase2",
    "engine.certificate",
    "engine.segment_merge",
)


def _span(name):
    h = obs.REGISTRY.find(f"span.{name}")
    return (h.count, h.sum) if h is not None else (0, 0.0)


def test_fused_dispatch_child_spans_once_each():
    """One quantized fused dispatch opens each step's child span exactly
    once, inside `engine.dispatch`: their seconds sum to no more than
    the parent's, and the f32 rerun runs only after a failed
    certificate."""
    rng = np.random.default_rng(21)
    idx = StreamingIndex(
        StreamingConfig(
            dim=3, delta_capacity=64, spec=SPEC, storage_dtype="bfloat16"
        )
    )
    idx.bulk_load(rng.standard_normal((300, 3)))
    snap = idx.snapshot()
    queries = rng.standard_normal((5, 3))
    spec = QuerySpec(k=4, radius=1.0)
    qengine.execute(snap, queries, spec)  # compile outside the count
    obs.reset()
    qengine.execute(snap, queries, spec)
    assert _span("engine.dispatch")[0] == 1
    for name in FUSED_CHILD_SPANS:
        assert _span(name)[0] == 1, name
    assert _span("engine.classic")[0] == 0
    reruns = obs.REGISTRY.counter("quantized.rescore", result="fallback").value
    assert _span("engine.f32_rerun")[0] == reruns
    children = sum(
        _span(n)[1] for n in FUSED_CHILD_SPANS + ("engine.f32_rerun",)
    )
    assert children <= _span("engine.dispatch")[1]
    # the host copies of the merged answer sit inside engine.merge
    assert _span("engine.readback")[0] == _span("engine.merge")[0] == 1


def test_classic_dispatch_span(monkeypatch):
    """The classic path (overflow fallback or the escape hatch) runs in
    `engine.classic`, and phase 2 counts no slots for it."""
    monkeypatch.setenv("REPRO_FUSED_TRAVERSAL", "0")
    rng = np.random.default_rng(22)
    idx = _small_index(rng, segments=1, delta=False)
    qengine.execute(idx.snapshot(), rng.standard_normal((3, 3)),
                    QuerySpec(k=2, radius=1.0))
    assert _span("engine.classic")[0] == _span("engine.dispatch")[0] == 1
    assert _span("engine.phase1")[0] == 0
    for kind in ("frontier", "evaluated"):
        assert obs.REGISTRY.counter("engine.phase2_slots", kind=kind).value == 0


def test_phase2_slot_counters_match_host_frontier():
    """`engine.phase2_slots{kind=frontier}` adds the real frontier
    leaves (the host `leaf_frontier` oracle's sizes); `{kind=evaluated}`
    the slots phase 2 evaluates, S_pow2 · Q · f_eff (3 same-class
    segments stack to 4; f_eff is the pow2 class of the widest
    frontier)."""
    rng = np.random.default_rng(23)
    idx = StreamingIndex(StreamingConfig(dim=3, delta_capacity=64, spec=SPEC))
    for _ in range(3):
        idx.bulk_load(rng.standard_normal((52, 3)))
    snap = idx.snapshot()
    groups = qengine.plan(snap)
    assert [len(g.views) for g in groups] == [3]
    queries = rng.standard_normal((6, 3))
    k, r = 4, 1.5
    widths = [
        len(sh.leaf_frontier(seg.tree, q, k, r))
        for seg in idx.segments
        for q in queries
    ]
    f_eff = 1
    while f_eff < max(widths):
        f_eff *= 2
    qengine.execute(snap, queries, QuerySpec(k=k, radius=r))
    slots = {
        kind: obs.REGISTRY.find("engine.phase2_slots", kind=kind).value
        for kind in ("frontier", "evaluated")
    }
    assert slots == {"frontier": sum(widths),
                     "evaluated": 4 * len(queries) * f_eff}


def test_jit_build_seconds_by_function():
    """A fresh jitted function, compiled once, adds one count under its
    `fun` label to each of `jit.trace_s`, `jit.lower_s` and
    `jit.compile_s`; a second call builds nothing and adds none."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _obs_fresh_program(x):
        return x * 3.0 + 1.0

    def counts():
        return [
            getattr(obs.REGISTRY.find(n, fun="_obs_fresh_program"), "count", 0)
            for n in ("jit.trace_s", "jit.lower_s", "jit.compile_s")
        ]

    x = jnp.arange(4.0)
    _obs_fresh_program(x).block_until_ready()
    assert counts() == [1, 1, 1]
    for n in ("jit.trace_s", "jit.lower_s", "jit.compile_s"):
        h = obs.REGISTRY.find(n, fun="_obs_fresh_program")
        assert h.unit == "s" and h.sum >= 0.0
    _obs_fresh_program(x).block_until_ready()
    assert counts() == [1, 1, 1]
