"""ShardedStreamingIndex: the streaming LSM sharded over the `data` axis.

Each shard is a complete `StreamingIndex` — its own delta arena,
segment set, tombstone log, and (optionally) WAL — pinned to one device
of a 1-D ``data`` mesh. Global ids are assigned at THIS layer in
insertion order, i.e. the very ids a single-device index would assign
for the same operation sequence, and points are routed round-robin
(``shard = gid % n_shards``), so shard sizes stay balanced to within
one point per batch and a sharded index is comparable bit-for-bit
against an unsharded one over any randomized interleave of operations.

Search fans out, then folds:

  1. every shard's snapshot runs through the unified query engine
     planner independently (`query/engine.execute`), on its own device;
     shards stamp their snapshots with a distinct ``cache_tag`` so
     same-shape-class batches from different shards occupy different
     buckets of the engine's stacked-batch LRU instead of evicting
     each other;
  2. per-shard LOCAL ids are translated to global ids on the host via
     the layer's append-only local→global tables;
  3. the per-shard sorted k-bests are folded with the engine's own
     merge primitive (`query/merge.merge_parts`) — under ``shard_map``
     over the data axis when the mesh has the devices (each shard
     `all_gather`s the (S, Q, k) parts and folds, outputs replicated),
     or as a host-driven fold on the default device when it does not
     (single-device test runs). Both paths are exact for the standard
     reason: every live point lives in exactly one shard, each shard's
     k-best is exact over its own points, and the union of per-shard
     k-bests is a superset of the global k-best.

Recovery: with ``wal_dir`` set every shard writes its own WAL (and its
own checkpoint), and this layer stamps each add/bulk_load record's
``meta`` with the chunk's global ids. A restart recovers each shard in
its `StreamingIndex` constructor (checkpoint + WAL-tail replay) and
rebuilds the global↔local translation here from the shard's replayed
meta stream (`StreamingIndex.wal_metas`) — the local ids a shard
assigns are contiguous in meta order, exactly matching the order the
metas were recorded in.

Degraded mode: per-shard search dispatches run under a `FailoverPolicy`
— transient failures are retried with exponential backoff, a shard that
stays down is skipped with the query's result flagged ``partial=True``
and the failover counted on the obs registry, and only an all-shard
failure raises. The fault-injection site ``shard.search`` lets tests
and the chaos bench drive exactly these paths deterministically.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.query import merge as qmerge
from repro.query.spec import QuerySpec

from . import faults
from . import search as search_mod
from .snapshot import Snapshot
from .streaming import StreamingConfig, StreamingIndex


@dataclasses.dataclass(frozen=True)
class FailoverPolicy:
    """Degraded-mode search policy: a failing shard dispatch is retried
    with exponential backoff, then — when `enabled` — skipped, with the
    query's result flagged ``partial=True`` and the skip counted on the
    obs registry (``shard.failovers``). Disabled, the failure propagates
    to the caller after the retries (strict mode); a query only ever
    raises in degraded mode when EVERY shard fails."""

    enabled: bool = True
    max_retries: int = 2
    backoff_s: float = 0.005
    backoff_multiplier: float = 2.0


def data_mesh(n_shards: int, axis: str = "data") -> Optional[Mesh]:
    """A 1-D mesh of `n_shards` devices over `axis`, or None when the
    process doesn't have that many devices (host-fold fallback)."""
    devs = jax.devices()
    if len(devs) < n_shards:
        return None
    return Mesh(np.asarray(devs[:n_shards]), (axis,))


class ShardedSnapshot(NamedTuple):
    """Consistent-enough multi-shard read view: per-shard MVCC
    snapshots (each individually torn-free) plus the local→global
    translation tables frozen at capture."""

    shards: Tuple[Snapshot, ...]
    g_of: Tuple[np.ndarray, ...]  # g_of[s][local_gid] = global gid

    @property
    def n_live(self) -> int:
        return sum(s.n_live for s in self.shards)


class ShardedStreamingIndex:
    def __init__(
        self,
        config: StreamingConfig,
        n_shards: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        wal_dir: Optional[str] = None,
        axis: str = "data",
        failover: Optional[FailoverPolicy] = None,
    ) -> None:
        self.failover = failover if failover is not None else FailoverPolicy()
        if mesh is not None and axis in mesh.shape:
            n_shards = n_shards or int(mesh.shape[axis])
        self.n_shards = int(n_shards or max(1, len(jax.devices())))
        if self.n_shards < 1:
            raise ValueError("need n_shards >= 1")
        self._axis = axis
        self._mesh = mesh if mesh is None else self._check_mesh(mesh)
        if self._mesh is None:
            self._mesh = data_mesh(self.n_shards, axis)
        # device pinning: each shard's arena/segments live on (and its
        # searches dispatch to) its own device; best-effort round-robin
        # when the process has fewer devices than shards
        devs = (
            list(self._mesh.devices.flat)
            if self._mesh is not None
            else jax.devices()
        )
        self._devices = [devs[s % len(devs)] for s in range(self.n_shards)]
        self._lock = threading.RLock()
        self._wal_dir = wal_dir
        if wal_dir:
            os.makedirs(wal_dir, exist_ok=True)

        self.config = config
        self._shards: List[StreamingIndex] = []
        for s in range(self.n_shards):
            sub_cfg = dataclasses.replace(
                config,
                wal_path=(
                    os.path.join(wal_dir, f"shard{s:03d}.wal")
                    if wal_dir
                    else None
                ),
            )
            with jax.default_device(self._devices[s]):
                sub = StreamingIndex(sub_cfg)  # replays its WAL if any
            sub.cache_tag = ("shard", id(self), s)
            self._shards.append(sub)

        # append-only local→global tables + the inverse locator; both
        # cover every id EVER assigned (deletes are tombstones)
        self._g_of: List[List[int]] = [[] for _ in range(self.n_shards)]
        self._g_arr: List[np.ndarray] = [
            np.empty(0, np.int64) for _ in range(self.n_shards)
        ]
        self._local_of: Dict[int, int] = {}
        self._next_gid = 0
        if wal_dir:
            self._recover_translation()
        self._fold_fns: dict = {}

    def _check_mesh(self, mesh: Mesh) -> Mesh:
        if self._axis not in mesh.shape:
            raise ValueError(f"mesh has no {self._axis!r} axis")
        if int(mesh.shape[self._axis]) != self.n_shards:
            raise ValueError(
                f"mesh {self._axis} size {mesh.shape[self._axis]} != "
                f"n_shards {self.n_shards}"
            )
        return mesh

    def _recover_translation(self) -> None:
        """Rebuild global↔local tables from each shard's replayed meta
        stream (`StreamingIndex.wal_metas`: the checkpoint-restored
        prefix plus the WAL-tail replay, in the shard's local-id
        assignment order). The WAL files alone no longer suffice —
        checkpoint truncation drops the covered records — but the meta
        stream is part of the checkpoint payload, so positions still
        line up by construction."""
        for s, sub in enumerate(self._shards):
            for meta in sub.wal_metas:
                if meta is None:
                    raise ValueError(
                        "sharded WAL record lacks global-gid meta; "
                        "was this log written by a bare StreamingIndex?"
                    )
                self._register(s, np.asarray(meta, np.int64))
        if any(len(g) for g in self._g_of):
            self._next_gid = max(
                int(g[-1]) for g in self._g_of if len(g)
            ) + 1

    def _register(self, s: int, global_gids: np.ndarray) -> None:
        base = len(self._g_of[s])
        self._g_of[s].extend(int(g) for g in global_gids)
        for i, g in enumerate(global_gids):
            self._local_of[int(g)] = base + i

    def _g_table(self, s: int) -> np.ndarray:
        if len(self._g_arr[s]) != len(self._g_of[s]):
            self._g_arr[s] = np.asarray(self._g_of[s], np.int64)
        return self._g_arr[s]

    # -- introspection -------------------------------------------------------
    @property
    def dim(self) -> int:
        return self.config.dim

    @property
    def n_live(self) -> int:
        return sum(sub.n_live for sub in self._shards)

    @property
    def shards(self) -> Tuple[StreamingIndex, ...]:
        return tuple(self._shards)

    def live_points(self):
        """All live (points, gids) sorted by GLOBAL gid — identical to
        what an unsharded index over the same op sequence reports."""
        parts_p, parts_g = [], []
        for s, sub in enumerate(self._shards):
            pts, local_g = sub.live_points()
            parts_p.append(pts)
            parts_g.append(self._g_table(s)[local_g])
        pts = np.concatenate(parts_p)
        gids = np.concatenate(parts_g)
        order = np.argsort(gids, kind="stable")
        return pts[order], gids[order]

    def stats(self) -> dict:
        per = [sub.stats() for sub in self._shards]
        return {
            "n_shards": self.n_shards,
            "n_live": self.n_live,
            "n_live_per_shard": [p["n_live"] for p in per],
            "n_segments_per_shard": [p["n_segments"] for p in per],
            "shards": per,
        }

    # -- write path (routes to shards, assigns GLOBAL gids) ------------------
    def add(self, points: np.ndarray) -> np.ndarray:
        pts = np.asarray(points, np.float32).reshape(-1, self.dim)
        with self._lock:
            gids = np.arange(
                self._next_gid, self._next_gid + len(pts), dtype=np.int64
            )
            self._next_gid += len(pts)
            for s, sub in enumerate(self._shards):
                mask = (gids % self.n_shards) == s
                if not mask.any():
                    continue
                with jax.default_device(self._devices[s]):
                    sub.add(pts[mask], meta=gids[mask])
                self._register(s, gids[mask])
        return gids

    def bulk_load(self, points: np.ndarray) -> np.ndarray:
        pts = np.asarray(points, np.float32).reshape(-1, self.dim)
        with self._lock:
            gids = np.arange(
                self._next_gid, self._next_gid + len(pts), dtype=np.int64
            )
            self._next_gid += len(pts)
            for s, sub in enumerate(self._shards):
                mask = (gids % self.n_shards) == s
                if not mask.any():
                    continue
                with jax.default_device(self._devices[s]):
                    sub.bulk_load(pts[mask], meta=gids[mask])
                self._register(s, gids[mask])
        return gids

    def delete(self, gids: np.ndarray) -> int:
        g = np.atleast_1d(np.asarray(gids, np.int64))
        n = 0
        with self._lock:
            for s, sub in enumerate(self._shards):
                mine = g[g % self.n_shards == s]
                locs = [
                    self._local_of[int(x)]
                    for x in mine
                    if int(x) in self._local_of
                ]
                if not locs:
                    continue
                with jax.default_device(self._devices[s]):
                    n += sub.delete(np.asarray(locs, np.int64))
        return n

    def flush(self) -> None:
        with self._lock:
            for s, sub in enumerate(self._shards):
                with jax.default_device(self._devices[s]):
                    sub.flush()

    def compact(self) -> None:
        with self._lock:
            for s, sub in enumerate(self._shards):
                with jax.default_device(self._devices[s]):
                    sub.compact()

    def maintain(self) -> bool:
        changed = False
        for s, sub in enumerate(self._shards):
            with jax.default_device(self._devices[s]):
                changed |= sub.maintain()
        return changed

    def checkpoint(self) -> bool:
        """Checkpoint every shard (each truncates its own WAL). True if
        any shard published one (False on volatile shards)."""
        ok = False
        with self._lock:
            for s, sub in enumerate(self._shards):
                with jax.default_device(self._devices[s]):
                    ok |= sub.checkpoint()
        return ok

    def start_background_compaction(self, interval: float = 0.05) -> None:
        for sub in self._shards:
            sub.start_background_compaction(interval)

    def stop_background_compaction(self) -> None:
        self._on_every_shard("stop_background_compaction")

    def close(self) -> None:
        self._on_every_shard("close")

    def _on_every_shard(self, method: str) -> None:
        """Call `method` on every shard even when one raises (a shard
        re-raises its background compaction failure), then re-raise
        the first failure."""
        errors = []
        for sub in self._shards:
            try:
                getattr(sub, method)()
            except Exception as e:
                errors.append(e)
        if errors:
            raise errors[0]

    # -- read path -----------------------------------------------------------
    def snapshot(self) -> ShardedSnapshot:
        with self._lock:
            return ShardedSnapshot(
                shards=tuple(sub.snapshot() for sub in self._shards),
                g_of=tuple(
                    self._g_table(s) for s in range(self.n_shards)
                ),
            )

    def _search_shard(self, s: int, sub_snap: Snapshot, q, spec):
        """One shard's engine dispatch with the failover retry loop.
        `faults.fire` is INSIDE the loop, so a transient injected fault
        (`times=1`) clears on retry exactly like a real flaky device."""
        from repro.query import engine as qengine

        pol = self.failover
        attempts = 1 + max(0, pol.max_retries)
        delay = pol.backoff_s
        for attempt in range(attempts):
            try:
                faults.fire("shard.search", shard=s)
                with jax.default_device(self._devices[s]):
                    return qengine.execute(sub_snap, q, spec)
            except Exception:
                if attempt + 1 >= attempts:
                    raise
                obs.REGISTRY.counter("shard.search_retries", shard=s).inc()
                time.sleep(delay)
                delay *= pol.backoff_multiplier

    def constrained_knn(
        self, queries: np.ndarray, k: int, r
    ) -> search_mod.StreamResult:
        """Exact constrained-KNN over all shards' live points.

        Degraded mode (`FailoverPolicy.enabled`, the default): a shard
        whose dispatch keeps failing after the retry budget is skipped
        — its skip is counted (``shard.failovers``) and the result is
        flagged ``partial=True`` — instead of failing the whole query.
        Only when EVERY shard fails does the query raise."""
        snap = self.snapshot()
        q = np.asarray(queries, np.float32).reshape(-1, self.dim)
        spec = QuerySpec(k=k, radius=r)
        parts_d, parts_g = [], []
        failed = 0
        last_err: Optional[BaseException] = None
        for s, sub_snap in enumerate(snap.shards):
            try:
                res = self._search_shard(s, sub_snap, q, spec)
            except Exception as e:
                if not self.failover.enabled:
                    raise
                failed += 1
                last_err = e
                obs.REGISTRY.counter("shard.failovers", shard=s).inc()
                continue
            local = np.asarray(res.gids, np.int64)
            glob = np.full_like(local, -1)
            valid = local >= 0
            glob[valid] = snap.g_of[s][local[valid]]
            parts_d.append(np.asarray(res.distances, np.float32))
            parts_g.append(glob)
        if not parts_d:
            raise RuntimeError(
                f"all {self.n_shards} shards failed"
            ) from last_err
        partial = failed > 0
        if partial:
            obs.REGISTRY.counter("shard.partial_queries").inc()
        d, g = self._fold(parts_d, parts_g, k)
        return search_mod.StreamResult(
            gids=np.asarray(g, np.int64),
            distances=np.asarray(d, np.float32),
            partial=partial,
        )

    def knn(self, queries: np.ndarray, k: int) -> search_mod.StreamResult:
        return self.constrained_knn(queries, k, np.inf)

    # -- cross-shard fold ----------------------------------------------------
    def _fold(self, parts_d, parts_g, k: int):
        """Fold per-shard sorted k-bests into the global k-best with the
        engine's merge primitive — inside `shard_map` over the data
        axis when the mesh is real AND every shard answered, else on
        the default device (a degraded query's surviving parts no
        longer fill the mesh's data axis)."""
        if len(parts_d) == 1:
            return parts_d[0], parts_g[0]
        # global gids stay < 2^31 (TombstoneLog guards assignment), so
        # the i32 merge lanes are safe
        if self._mesh is not None and len(parts_d) == self.n_shards:
            dd = np.stack(parts_d)                      # (S, Q, k) f32
            gg = np.stack(parts_g).astype(np.int32)     # (S, Q, k) i32
            fold = self._fold_fns.get(k)
            if fold is None:
                fold = self._make_fold(k)
                self._fold_fns[k] = fold
            sharding = NamedSharding(self._mesh, P(self._axis))
            d, g = fold(
                jax.device_put(dd, sharding), jax.device_put(gg, sharding)
            )
            return d, g
        parts = [
            (jnp.asarray(d), jnp.asarray(g.astype(np.int32)))
            for d, g in zip(parts_d, parts_g)
        ]
        return qmerge.merge_parts(parts, k)

    def _make_fold(self, k: int):
        mesh, axis, S = self._mesh, self._axis, self.n_shards

        def _local(d_l, g_l):  # (1, Q, k) per-shard block
            all_d = jax.lax.all_gather(d_l, axis)  # (S, 1, Q, k)
            all_g = jax.lax.all_gather(g_l, axis)
            return qmerge.merge_parts(
                [(all_d[s, 0], all_g[s, 0]) for s in range(S)], k
            )

        fold = jax.shard_map(
            _local,
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return jax.jit(fold)


__all__ = [
    "FailoverPolicy",
    "ShardedSnapshot",
    "ShardedStreamingIndex",
    "data_mesh",
]
