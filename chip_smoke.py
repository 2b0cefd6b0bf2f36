#!/usr/bin/env python3
"""Bring-up smoke of the served constrained-kNN path on a TPU.

One chip (no arguments): the paper's 3D Road Network scale (434,874
points, d=4, 5,300 uniform queries, r = 5% of the bounding-box
diagonal, k=8) goes through the normal entry points:

  1. load   `StreamingIndex.bulk_load` with bf16 segment storage, a WAL
            and a checkpoint path under ``smoke_state/``;
  2. serve  a `SearchFrontend` (warmup compiles every batch class) takes
            all queries from 8 client threads; every reply is checked
            against `core/brute.py` over the live set;
  3. write  ~50,000 inserts in batches (fills the delta arena and drives
            the donated in-place append) and ~10% deletes, with
            `maintain()` run until it returns False after every op
            (merges + auto-checkpoints), while a reader thread re-runs
            queries on snapshots it holds and checks they never change;
            then `flush()`, `maintain()` again, and a fresh batch is
            served and checked against the oracle.

Four chips (``--chips 4``): only the sharded phase — a
`ShardedStreamingIndex` with 4 shards over a real 4-device ``data``
mesh, strict failover, fed the same load (100,000 points), inserts
(10,000) and deletes as a single-device `StreamingIndex`; after `flush()` the two answer
bit-identically and both match the brute oracle. Each shard's arrays
must sit on their own device, and no shard may fail over.

Exits nonzero, without the result line, when JAX finds no TPU, when the
repository's modules are missing, or when any phase or check fails. On
success the last line of stdout is one JSON object naming the device.
Times printed are host-clock smoke timings, not benchmark results.

    python chip_smoke.py
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
STATE_DIR = os.path.join(ROOT, "smoke_state")


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_points: int = 434_874   # UCI 3D Road Network
    n_queries: int = 5_300    # the paper's query count
    n_insert: int = 50_000
    insert_batch: int = 2_000
    delete_frac: float = 0.10
    n_fresh: int = 1_024      # post-write queries
    k: int = 8
    radius_frac: float = 0.05
    clients: int = 8
    seed: int = 0


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


# -- data and oracle ------------------------------------------------------


def make_data(sz: Sizes):
    from benchmarks.common import radius_for
    from repro.data import synthetic

    pts = synthetic.make("3d-road-network", sz.n_points, seed=sz.seed)
    queries = synthetic.uniform_queries(pts, sz.n_queries, seed=sz.seed + 1)
    r = radius_for(pts, sz.radius_frac)
    return (
        pts.astype("float32"),
        queries.astype("float32"),
        float(r),
    )


def _verdict(g, d, q, live_pts, live_g, k, r):
    """One reply against `brute.constrained_knn` over the live set,
    compared as `tests/test_streaming_index.py` does: same count,
    distances within rtol 1e-4, same id set ("ok"). The oracle runs in
    f64 and the index in f32, so ids whose distances tie with the k-th
    one, or with r, to within 1e-6 may trade places; such a reply
    passes as a "tie" only if it holds every point clearly inside that
    edge, no point beyond it, and distances true to rtol 1e-4."""
    import numpy as np

    from repro.core import brute

    bi, bd = brute.constrained_knn(live_pts, q, k, r)
    valid = g >= 0
    gv, dv = g[valid], d[valid]
    if (len(gv) == len(bi) and np.allclose(dv, bd, rtol=1e-4, atol=1e-5)
            and set(gv.tolist()) == set(live_g[bi].tolist())):
        return "ok"
    eps = 1e-6
    dist = np.sqrt(((live_pts - q) ** 2).sum(axis=1))
    by_gid = dict(zip(live_g.tolist(), dist.tolist()))
    true = np.asarray([by_gid.get(int(x), np.inf) for x in gv])
    edge = min(bd[-1] if len(bd) == k else np.inf, r)
    sure = set(live_g[dist < edge * (1 - eps)].tolist())
    tie = (
        len(set(gv.tolist())) == len(gv) <= k
        and np.isfinite(true).all()
        and np.allclose(dv, true, rtol=1e-4, atol=1e-5)
        and bool((true <= edge * (1 + eps)).all())
        and sure <= set(gv.tolist())
    )
    return "tie" if tie else "bad"


def oracle_mismatches(gids, dists, queries, live_pts, live_g, k, r):
    """(rows that fail `_verdict`, count of boundary-tie rows)."""
    import numpy as np

    qs = np.asarray(queries, np.float64)
    with ThreadPoolExecutor(max_workers=os.cpu_count() or 4) as ex:
        verdicts = list(ex.map(
            lambda i: _verdict(gids[i], dists[i], qs[i], live_pts, live_g,
                               k, r),
            range(len(qs)),
        ))
    bad = [i for i, v in enumerate(verdicts) if v == "bad"]
    return bad, verdicts.count("tie")


def check_oracle(label, gids, dists, queries, index, k, r):
    import numpy as np

    live_pts, live_g = index.live_points()
    bad, ties = oracle_mismatches(
        gids, dists, queries, live_pts.astype(np.float64), live_g, k, r
    )
    say(f"{label}: {len(queries)} replies vs brute oracle over "
        f"{len(live_g)} live points: {len(bad)} mismatches, "
        f"{ties} boundary ties")
    check(not bad, f"{label}: rows {bad[:10]} differ from the oracle")


# -- serving --------------------------------------------------------------


def serve_all(index, queries, sz: Sizes, r: float):
    """All queries through a fresh `SearchFrontend` from `sz.clients`
    threads; returns (gids, dists, partial flags, warmup s, serve s)."""
    import numpy as np

    from repro.serve.frontend import FrontendConfig, SearchFrontend

    t0 = time.perf_counter()
    fe = SearchFrontend(index, FrontendConfig(k=sz.k, radius=r)).start()
    t_warm = time.perf_counter() - t0
    gids = np.full((len(queries), sz.k), -2, np.int64)
    dists = np.zeros((len(queries), sz.k), np.float32)
    partial = np.zeros(len(queries), bool)

    def client(c):
        rows = range(c, len(queries), sz.clients)
        futs = [(i, fe.submit(queries[i])) for i in rows]
        for i, f in futs:
            rep = f.result()
            gids[i], dists[i], partial[i] = rep.gids, rep.distances, rep.partial

    t1 = time.perf_counter()
    try:
        with ThreadPoolExecutor(max_workers=sz.clients) as ex:
            list(ex.map(client, range(sz.clients)))
    finally:
        fe.stop()
    return gids, dists, partial, t_warm, time.perf_counter() - t1


def kernels_compiled(k: int) -> list:
    """The served kernels lower to Mosaic custom calls, not to the
    interpreter: lower each `ops` wrapper as the served path calls it
    and look for `tpu_custom_call`. Returns the kernels that did not."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    check(not ops._interpret(), "kernels would run in interpret mode")
    q = jnp.zeros((64, 4), jnp.float32)
    c16 = jnp.zeros((64, 256, 4), jnp.bfloat16)
    c32 = jnp.zeros((64, 256, 4), jnp.float32)
    g = jnp.zeros((64, 256), jnp.int32)
    r = jnp.ones((64,), jnp.float32)
    lowered = {
        "topk_l2": jax.jit(
            lambda q, p, g: ops.topk_l2(q, p, g, r, k)
        ).lower(q, jnp.zeros((1024, 4)), jnp.zeros((1024,), jnp.int32)),
        "leaf_topk_l2": jax.jit(
            lambda q, c, g: ops.leaf_topk_l2(q, c, g, r, k, bk=128)
        ).lower(q, c32, g),
        "leaf_topk_l2_raw": jax.jit(
            lambda q, c, g: ops.leaf_topk_l2_raw(q, c, g, r, k + 8, bk=128)
        ).lower(q, c16, g),
    }
    return [n for n, lo in lowered.items() if "tpu_custom_call" not in lo.as_text()]


# -- write phase ----------------------------------------------------------


class SnapshotReader(threading.Thread):
    """Re-runs one query batch on snapshots it holds while the writer
    runs: a snapshot's answer must never change, whatever the writer
    appends (donated in place), seals, merges or tombstones."""

    def __init__(self, index, queries, k, r):
        super().__init__(name="smoke-snapshot-reader", daemon=True)
        self.index, self.queries, self.k, self.r = index, queries, k, r
        self.stop = threading.Event()
        self.checks = 0
        self.error = None

    def run(self):
        import numpy as np

        from repro.index import search as search_mod

        try:
            while not self.stop.is_set():
                snap = self.index.snapshot()
                a = search_mod.constrained_knn(snap, self.queries, self.k, self.r)
                self.stop.wait(0.05)
                b = search_mod.constrained_knn(snap, self.queries, self.k, self.r)
                if not (np.array_equal(a.gids, b.gids)
                        and np.array_equal(a.distances, b.distances)):
                    raise SmokeFailure(
                        f"snapshot v{snap.version} answered differently "
                        f"after concurrent writes"
                    )
                self.checks += 1
        except BaseException as e:  # reported by the writer thread
            self.error = e


def write_ops(sz: Sizes, pts):
    """The write workload as a list of ("add", points) / ("delete",
    gids) ops: inserts in batches, with ~delete_frac of the live points
    deleted in slices between them. Gids are predictable because every
    index assigns them in insertion order."""
    import numpy as np

    from repro.data import synthetic

    rng = np.random.default_rng(sz.seed + 2)
    new = synthetic.make("3d-road-network", sz.n_insert, seed=sz.seed + 3)
    new = new.astype(np.float32)
    n_total = len(pts) + sz.n_insert
    victims = rng.choice(n_total, int(sz.delete_frac * n_total), replace=False)
    n_batches = -(-sz.n_insert // sz.insert_batch)
    slices = np.array_split(victims, n_batches)
    out, added = [], len(pts)
    for b in range(n_batches):
        chunk = new[b * sz.insert_batch:(b + 1) * sz.insert_batch]
        out.append(("add", chunk))
        added += len(chunk)
        dead = slices[b][slices[b] < added]  # only ids assigned so far
        late = slices[b][slices[b] >= added]
        if b + 1 < n_batches:
            slices[b + 1] = np.concatenate([slices[b + 1], late])
        if len(dead):
            out.append(("delete", dead))
    return out


def apply_ops(index, ops_list):
    """Apply the write workload. Merges are deferred, so the writer runs
    the pending ones in the foreground after every op (each merge also
    auto-checkpoints a durable index): segment counts per shape class
    stay at the tiering bound, as with inline merging."""
    for op, arg in ops_list:
        if op == "add":
            index.add(arg)
        else:
            index.delete(arg)
        while index.maintain():
            pass


# -- phases ---------------------------------------------------------------


def obs_counter(name, **labels) -> int:
    from repro import obs

    m = obs.REGISTRY.find(name, **labels)
    return int(m.value) if m is not None else 0


def one_chip(sz: Sizes) -> None:
    import numpy as np

    from repro.data import synthetic
    from repro.index import StreamingConfig, StreamingIndex

    t = time.perf_counter()
    pts, queries, r = make_data(sz)
    say(f"data: 3d-road-network n={len(pts)} d={pts.shape[1]} "
        f"queries={len(queries)} r={r!r} k={sz.k} "
        f"(generated in {time.perf_counter() - t:.3f} s)")

    shutil.rmtree(STATE_DIR, ignore_errors=True)
    os.makedirs(STATE_DIR)
    cfg = StreamingConfig(
        dim=pts.shape[1],
        wal_path=os.path.join(STATE_DIR, "index.wal"),
        checkpoint_path=os.path.join(STATE_DIR, "index.ckpt"),
        defer_merges=True,
    )
    check(cfg.storage_dtype == "bfloat16", f"storage {cfg.storage_dtype}")
    index = StreamingIndex(cfg)
    t = time.perf_counter()
    index.bulk_load(pts)
    say(f"smoke timing (host clock, not a benchmark): load "
        f"{time.perf_counter() - t:.3f} s")

    gids, dists, partial, t_warm, t_serve = serve_all(index, queries, sz, r)
    say(f"smoke timing (host clock, not a benchmark): warmup "
        f"{t_warm:.3f} s, serve {len(queries)} queries {t_serve:.3f} s")
    check(not partial.any(), "a single index returned a partial reply")
    check_oracle("serve", gids, dists, queries, index, sz.k, r)

    missing = kernels_compiled(sz.k)
    check(not missing, f"kernels not lowered to Mosaic: {missing}")
    say("kernels lowered to tpu_custom_call: topk_l2, leaf_topk_l2, "
        "leaf_topk_l2_raw")

    # -- write phase with snapshot readers --------------------------------
    reader = SnapshotReader(index, queries[:64], sz.k, r)
    t = time.perf_counter()
    reader.start()
    try:
        apply_ops(index, write_ops(sz, pts))
    finally:
        reader.stop.set()
        reader.join()
    if reader.error is not None:
        raise reader.error
    check(reader.checks > 0, "the snapshot reader never completed a check")
    index.flush()
    while index.maintain():
        pass
    st = index.stats()
    say(f"smoke timing (host clock, not a benchmark): write phase "
        f"{time.perf_counter() - t:.3f} s; {reader.checks} snapshot "
        f"re-reads unchanged; seals {st['seals']} merges "
        f"{st['tiered_merges'] + st['purge_merges']} checkpoints "
        f"{st['checkpoints']}; segments {st['n_segments']} live "
        f"{st['n_live']}")
    check(st["delta_fill"] == 0, "flush left points in the delta")
    check(st["checkpoints"] > 0, "maintain() wrote no checkpoint")

    fresh = synthetic.uniform_queries(pts, sz.n_fresh, seed=sz.seed + 4)
    fresh = fresh.astype(np.float32)
    gids, dists, partial, t_warm, t_serve = serve_all(index, fresh, sz, r)
    say(f"smoke timing (host clock, not a benchmark): re-warmup "
        f"{t_warm:.3f} s, serve {len(fresh)} queries {t_serve:.3f} s")
    check(not partial.any(), "a single index returned a partial reply")
    check_oracle("post-write", gids, dists, fresh, index, sz.k, r)
    index.close()

    inplace = obs_counter("delta.double_buffer", path="inplace")
    say(f"delta.double_buffer{{path=inplace}}={inplace} "
        f"{{path=copy}}={obs_counter('delta.double_buffer', path='copy')}")
    check(inplace > 0, "the donated in-place append never ran")
    report_counters()
    shutil.rmtree(STATE_DIR, ignore_errors=True)


def four_chips(sz: Sizes) -> None:
    import numpy as np

    from repro.index import StreamingConfig, StreamingIndex
    from repro.index.sharded import (
        FailoverPolicy,
        ShardedStreamingIndex,
        data_mesh,
    )

    # four chips cost four times as much per second: a quarter of the
    # load and a fifth of the inserts (still several arena fills per
    # shard), the same shapes and mix
    sz = dataclasses.replace(
        sz, n_points=100_000, n_queries=sz.n_fresh, n_insert=10_000
    )
    pts, queries, r = make_data(sz)
    say(f"data: 3d-road-network n={len(pts)} queries={len(queries)} "
        f"r={r!r} k={sz.k}")
    mesh = data_mesh(4)
    check(mesh is not None, "data_mesh(4) is None: fewer than 4 devices")
    cfg = StreamingConfig(dim=pts.shape[1], defer_merges=True)
    sharded = ShardedStreamingIndex(
        cfg, mesh=mesh, failover=FailoverPolicy(enabled=False)
    )
    single = StreamingIndex(cfg)  # on the default device
    t = time.perf_counter()
    ops_list = write_ops(sz, pts)
    for idx in (sharded, single):
        idx.bulk_load(pts)
        apply_ops(idx, ops_list)
    for idx in (sharded, single):
        idx.flush()
        while idx.maintain():
            pass
    say(f"smoke timing (host clock, not a benchmark): load + writes on "
        f"both indexes {time.perf_counter() - t:.3f} s; live "
        f"{sharded.stats()['n_live_per_shard']} per shard")

    shard_devs = []
    for s, sub in enumerate(sharded.shards):
        arrays = [sub.delta.points, sub.delta.gids]
        for seg in sub.segments:
            arrays += [seg.gids_dev, *seg.dtree]
            if seg.leaf_q is not None:
                arrays.append(seg.leaf_q)
        devs = set().union(*(a.devices() for a in arrays))
        check(len(devs) == 1, f"shard {s} spans devices {devs}")
        shard_devs.append(devs.pop())
    check(len(set(shard_devs)) == 4, f"shards share devices: {shard_devs}")
    check(shard_devs == list(mesh.devices.flat),
          f"shard devices {shard_devs} are not the mesh's")
    say(f"shard devices: {[str(d) for d in shard_devs]}")

    gids, dists, partial, t_warm, t_serve = serve_all(sharded, queries, sz, r)
    say(f"smoke timing (host clock, not a benchmark): sharded warmup "
        f"{t_warm:.3f} s, serve {len(queries)} queries {t_serve:.3f} s")
    check(not partial.any(), "a sharded reply was partial")
    ref = [single.constrained_knn(queries[i:i + 64], sz.k, r)
           for i in range(0, len(queries), 64)]
    ref_g = np.concatenate([x.gids for x in ref])
    ref_d = np.concatenate([x.distances for x in ref])
    same = np.array_equal(gids, ref_g) and np.array_equal(dists, ref_d)
    say(f"sharded vs single-device after flush: bit-identical={same}")
    check(same, "sharded and single-device answers differ")
    check_oracle("sharded", gids, dists, queries, sharded, sz.k, r)
    failovers = [obs_counter("shard.failovers", shard=s) for s in range(4)]
    say(f"shard.failovers per shard: {failovers}")
    check(not any(failovers), "a shard failed over")
    report_counters()


def report_counters() -> None:
    from repro import obs

    snap = obs.REGISTRY.snapshot()["counters"]
    keep = ("kernel.calls", "engine.fused_traversal", "quantized.rescore",
            "engine.dispatches", "index.background_compaction_failures")
    for key in sorted(snap):
        if key.startswith(keep):
            say(f"counter {key} = {snap[key]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX sees {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: need {args.chips} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro import compile_cache

    say(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{compile_cache.configure()}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(Sizes())
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    stats = devs[0].memory_stats() or {}
    say(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")
    say(f"smoke timing (host clock, not a benchmark): total "
        f"{time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
