"""Streaming LSM index == fresh static build over the live point set,
under randomized interleaves of insert / delete / query (brute oracle),
plus compaction tombstone-purge and Datastore add/delete behaviour."""
import numpy as np
import pytest

from repro.core import TreeSpec, brute, build
from repro.core import search_jax as sj
from repro.index import StreamingConfig, StreamingIndex
from repro.serve.retrieval import Datastore

SPEC = TreeSpec.ballstar(leaf_size=8)


def make_index(dim, cap=64, factor=3):
    return StreamingIndex(
        StreamingConfig(
            dim=dim, delta_capacity=cap, spec=SPEC, merge_factor=factor
        )
    )


def check_oracle(idx, queries, k, r):
    """Index results == brute force over the index's own live point set."""
    pts, gids = idx.live_points()
    res = idx.constrained_knn(queries, k, r)
    for i, q in enumerate(queries):
        bi, bd = brute.constrained_knn(pts, q, k, r)
        valid = res.gids[i] >= 0
        assert valid.sum() == len(bi)
        np.testing.assert_allclose(
            res.distances[i][valid], bd, rtol=1e-4, atol=1e-5
        )
        assert set(res.gids[i][valid].tolist()) == set(gids[bi].tolist())


def test_delta_only_search():
    """Before the first seal every point lives in the device arena."""
    rng = np.random.default_rng(0)
    idx = make_index(3, cap=128)
    idx.add(rng.standard_normal((50, 3)))
    assert idx.stats()["n_segments"] == 0 and idx.stats()["delta_fill"] == 50
    check_oracle(idx, rng.standard_normal((6, 3)), k=5, r=1.2)
    check_oracle(idx, rng.standard_normal((4, 3)), k=3, r=np.inf)


def test_empty_and_overfull_k():
    idx = make_index(2, cap=16)
    res = idx.knn(np.zeros((2, 2)), k=4)
    assert (res.gids == -1).all() and np.isinf(res.distances).all()
    idx.add(np.random.default_rng(1).standard_normal((5, 2)))
    res = idx.knn(np.zeros((1, 2)), k=9)  # k > n_live
    assert (res.gids[0] >= 0).sum() == 5


def test_delta_search_pads_to_caller_k():
    """Regression for the fused-kernel rewire: an arena with capacity
    (or live count) below k must still answer in the caller's (Q, k)
    shape, padded with (+inf, -1) — the old host-side `kk < k` pad,
    now produced by the kernel itself."""
    from repro.index import delta as delta_mod

    rng = np.random.default_rng(3)
    buf = delta_mod.DeltaBuffer.empty(4, 2)  # capacity 4 < k=7
    buf = buf.append(
        rng.standard_normal((3, 2)), np.arange(10, 13)
    ).tombstone(np.asarray([1]))
    q = rng.standard_normal((5, 2)).astype(np.float32)
    dd, gg = delta_mod.search(buf.points, buf.gids, q, k=7, r=np.inf)
    assert dd.shape == (5, 7) and gg.shape == (5, 7)
    dd, gg = np.asarray(dd), np.asarray(gg)
    # exactly the 2 live points answer; the rest is (+inf, -1) padding
    assert ((gg >= 0).sum(axis=1) == 2).all()
    assert np.isinf(dd[:, 2:]).all() and (gg[:, 2:] == -1).all()
    assert set(gg[0, :2].tolist()) == {10, 12}


def test_interleaved_ops_match_oracle():
    """Randomized insert/delete/query interleave across seals and merges."""
    rng = np.random.default_rng(42)
    idx = make_index(3, cap=64, factor=3)
    queries = rng.standard_normal((5, 3))
    for step in range(12):
        idx.add(rng.standard_normal((rng.integers(20, 90), 3)))
        live = idx.live_gids()
        if step % 2 and len(live) > 30:
            idx.delete(rng.choice(live, size=len(live) // 6, replace=False))
        if step % 3 == 2:
            k = int(rng.integers(1, 9))
            r = float(rng.uniform(0.4, 2.5))
            check_oracle(idx, queries, k, r)
    st = idx.stats()
    assert st["n_segments"] >= 1  # seals + merges actually happened
    check_oracle(idx, queries, k=7, r=np.inf)


def test_matches_fresh_static_build():
    """Acceptance: streamed index == static ball*-tree on the live set."""
    rng = np.random.default_rng(7)
    idx = make_index(2, cap=64)
    g = idx.add(rng.standard_normal((300, 2)))
    idx.delete(g[::5])
    idx.add(rng.standard_normal((40, 2)))

    pts, gids = idx.live_points()
    tree = build(pts, SPEC, backend="jax")
    queries = rng.standard_normal((8, 2))
    k, r = 6, 0.9
    static = sj.search(tree, queries, k=k, r=r)
    stream = idx.constrained_knn(queries, k, r)
    d_static = np.asarray(static.distances)
    np.testing.assert_allclose(
        np.where(np.isinf(d_static), -1.0, d_static),
        np.where(np.isinf(stream.distances), -1.0, stream.distances),
        rtol=1e-4,
        atol=1e-5,
    )
    i_static = np.asarray(static.indices)  # local ids into `pts`
    for row_s, row_l in zip(i_static, stream.gids):
        assert {int(gids[j]) for j in row_s[row_s >= 0]} == set(
            row_l[row_l >= 0].tolist()
        )


def test_compaction_purges_tombstones():
    rng = np.random.default_rng(3)
    idx = make_index(2, cap=64)
    g = idx.add(rng.standard_normal((500, 2)))
    idx.delete(rng.choice(g, size=200, replace=False))
    queries = rng.standard_normal((6, 2))
    before = idx.constrained_knn(queries, 5, 1.0)

    idx.compact()
    st = idx.stats()
    assert st["n_segments"] == 1
    assert st["n_dead_in_segments"] == 0 and st["delta_fill"] == 0
    # physically stored == live: tombstones are gone, not just masked
    (seg,) = idx.segments
    assert seg.n_points == idx.n_live == 300
    after = idx.constrained_knn(queries, 5, 1.0)
    np.testing.assert_allclose(
        np.where(np.isinf(before.distances), -1.0, before.distances),
        np.where(np.isinf(after.distances), -1.0, after.distances),
        rtol=1e-4,
        atol=1e-5,
    )
    assert (before.gids == after.gids).all()
    check_oracle(idx, queries, k=5, r=1.0)


def test_tier_merges_bound_segment_count():
    """Size-tiered policy keeps the segment count logarithmic."""
    rng = np.random.default_rng(9)
    idx = make_index(2, cap=32, factor=2)
    for _ in range(16):
        idx.add(rng.standard_normal((32, 2)))
    st = idx.stats()
    # 512 points in 32-point seals under factor 2 -> log2(16) tiers max
    assert st["n_segments"] <= 5
    check_oracle(idx, rng.standard_normal((4, 2)), k=5, r=1.0)


def test_snapshot_isolation():
    """A reader's snapshot is immune to later writes (MVCC)."""
    rng = np.random.default_rng(11)
    idx = make_index(2, cap=64)
    idx.add(rng.standard_normal((100, 2)))
    snap = idx.snapshot()
    from repro.index import search as search_mod

    q = rng.standard_normal((3, 2))
    before = search_mod.constrained_knn(snap, q, 5, np.inf)
    idx.add(rng.standard_normal((80, 2)) + 5.0)
    idx.delete(idx.live_gids()[:50])
    after_old_snap = search_mod.constrained_knn(snap, q, 5, np.inf)
    assert (before.gids == after_old_snap.gids).all()
    np.testing.assert_allclose(
        before.distances, after_old_snap.distances, rtol=0, atol=0
    )
    assert idx.snapshot().version > snap.version


def test_snapshot_n_live_survives_delta_delete_then_add():
    """Regression: DeltaBuffer.append must carry n_dead through, else a
    delete-in-delta followed by an add overstates the snapshot's n_live."""
    from repro.index import search as search_mod

    rng = np.random.default_rng(13)
    idx = make_index(2, cap=32)
    g = idx.add(rng.standard_normal((20, 2)))  # delta only
    idx.delete(g[:5])
    idx.add(rng.standard_normal((10, 2)))      # append after tombstones
    snap = idx.snapshot()
    assert snap.n_live == idx.n_live == 25
    res = search_mod.knn(snap, np.zeros((1, 2)), k=40)
    assert int((res.gids[0] >= 0).sum()) == 25


def test_delete_idempotent_and_missing():
    idx = make_index(2, cap=32)
    g = idx.add(np.random.default_rng(0).standard_normal((10, 2)))
    assert idx.delete(g[:3]) == 3
    assert idx.delete(g[:3]) == 0  # already dead: no-op
    assert idx.delete(np.asarray([10_000])) == 0  # never existed
    assert idx.n_live == 7


def test_stats_counter_invariants():
    """Registry-backed lifetime counters obey the LSM bookkeeping
    identities under a randomized insert/delete/seal/merge workload."""
    rng = np.random.default_rng(21)
    cap, factor = 32, 3
    idx = make_index(2, cap=cap, factor=factor)
    n_added = n_deleted = 0
    for step in range(10):
        m = int(rng.integers(10, 70))
        idx.add(rng.standard_normal((m, 2)))
        n_added += m
        if step % 2:
            live = idx.live_gids()
            take = len(live) // 5
            if take:
                n_deleted += idx.delete(
                    rng.choice(live, size=take, replace=False)
                )
    st = idx.stats()
    assert st["inserts"] == n_added
    assert st["deletes"] == n_deleted
    assert st["n_live"] == n_added - n_deleted
    # every seal drains at most one arena's worth of live points, and
    # everything sealed was inserted first
    assert st["sealed_points"] <= st["seals"] * cap
    assert st["sealed_points"] <= st["inserts"]
    # every inserted point is either sealed, still in the arena, or was
    # tombstoned in the arena and dropped at a seal — so the ledger
    # never over-counts
    assert st["inserts"] >= st["sealed_points"] + st["delta_fill"]
    # a tiered merge folds >= factor inputs, a purge rebuild exactly one
    assert st["segments_merged"] >= (
        factor * st["tiered_merges"] + st["purge_merges"]
    )
    assert 0.0 <= st["tombstone_garbage_ratio"] <= 1.0
    # registry gauges mirror the live stats
    assert idx._g_n_segments.value == st["n_segments"]
    assert idx._g_delta_fill.value == st["delta_fill"]
    assert idx._g_version.value == st["version"]

    seals_before = st["seals"]
    idx.flush()
    st2 = idx.stats()
    assert st2["delta_fill"] == 0
    assert st2["seals"] >= seals_before
    assert st2["sealed_points"] <= st2["inserts"]

    if st2["tombstone_garbage_ratio"] == 0.0:
        idx.delete(idx.live_gids()[:10])
        st2 = idx.stats()
    assert st2["tombstone_garbage_ratio"] > 0.0
    idx.compact()
    st3 = idx.stats()
    assert st3["compactions"] == st2["compactions"] + 1
    assert st3["tombstone_garbage_ratio"] == 0.0
    assert idx._g_garbage.value == 0.0
    check_oracle(idx, rng.standard_normal((4, 2)), k=5, r=1.5)


def test_background_compaction_failure_counted_and_reraised(tmp_path):
    """A failure inside the background compaction loop is counted on
    obs and re-raised by stop_background_compaction(), not swallowed.
    The fault is injected at the auto-checkpoint that maintain() runs
    after a merge commits, so the merged state itself stays exact."""
    import time

    from repro.index import faults

    rng = np.random.default_rng(11)
    idx = StreamingIndex(
        StreamingConfig(
            dim=3, delta_capacity=16, spec=SPEC, merge_factor=2,
            defer_merges=True, wal_path=str(tmp_path / "idx.wal"),
        )
    )
    idx.add(rng.standard_normal((80, 3)))  # 5 seals, merges deferred
    with faults.active():
        faults.arm("checkpoint.step", times=1, exc=faults.InjectedCrash)
        idx.start_background_compaction(interval=0.01)
        deadline = time.monotonic() + 60.0
        while idx.stats()["background_compaction_failures"] == 0:
            if time.monotonic() > deadline:
                idx.stop_background_compaction()
                pytest.fail("the injected checkpoint fault never fired")
            time.sleep(0.01)
        with pytest.raises(faults.InjectedCrash):
            idx.stop_background_compaction()
    assert idx.stats()["background_compaction_failures"] == 1
    idx.stop_background_compaction()  # reported once, not again
    while idx.maintain():
        pass
    check_oracle(idx, rng.standard_normal((5, 3)), k=4, r=1.0)
    idx.close()


def test_datastore_add_delete_lookup():
    rng = np.random.default_rng(5)
    keys = rng.standard_normal((200, 4)).astype(np.float32)
    vals = rng.integers(0, 50, 200)
    store = Datastore.from_pairs(keys, vals, leaf_size=16, delta_capacity=64)
    assert store.n_keys == 200

    new_keys = rng.standard_normal((30, 4)).astype(np.float32)
    new_vals = rng.integers(50, 99, 30)
    gids = store.add(new_keys, new_vals)
    assert store.n_keys == 230
    # a query at a new key retrieves its own value
    nv, nd, ok = store.lookup(new_keys[:1], k=1, r=1e-3)
    assert ok[0, 0] and nv[0, 0] == new_vals[0]

    store.delete(gids)
    assert store.n_keys == 200
    nv, nd, ok = store.lookup(new_keys[:1], k=1, r=1e-3)
    assert not ok.any()  # evicted states no longer match


def test_from_pairs_spec_passthrough():
    keys = np.random.default_rng(2).standard_normal((100, 3)).astype(np.float32)
    vals = np.zeros(100, np.int64)
    spec = TreeSpec.kd(leaf_size=4)
    store = Datastore.from_pairs(keys, vals, spec=spec)
    assert store.index.config.spec is spec
    (seg,) = store.index.segments
    assert seg.tree.spec.splitter == "kd"
    assert seg.tree.spec.leaf_size == 4
    # default path still honours leaf_size convenience arg
    store2 = Datastore.from_pairs(keys, vals, leaf_size=16)
    assert store2.index.config.spec.splitter == "ballstar"
    assert store2.index.config.spec.leaf_size == 16
