"""Distributed ball*-tree: the paper's future-work ("parallel and
distributed implementations for modern hardware") done JAX-natively.

Scatter-gather sharding: the point set is split over the `data` mesh
axis, each shard builds a LOCAL ball*-tree over its points, and a query
runs the constrained-NN traversal in every shard simultaneously under
shard_map; the global K-best is an all_gather of each shard's local
K-best (K × (d+2) floats per query — tiny) followed by a top-K merge.
Exactness: the union of per-shard K-bests contains the global K-best,
so the merge is exact. Collective volume per query is O(shards · K),
independent of N — this is what lets the index scale to pods.

Build is embarrassingly parallel (each shard runs the level-synchronous
vectorized builder on its slice); no cross-shard communication at all.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.query import merge as qmerge

from . import build_jax, search_jax as sj
from .types import Tree, TreeSpec


@dataclasses.dataclass
class ShardedIndex:
    mesh: Mesh
    trees: List[Tree]            # host handles (one per shard)
    stacked: sj.DeviceTree       # leaves stacked on a leading shard axis
    stack_size: int
    shard_offsets: np.ndarray    # original-id offset per shard

    @property
    def n_shards(self) -> int:
        return len(self.trees)


def build_sharded(
    points: np.ndarray,
    mesh: Mesh,
    spec: TreeSpec | None = None,
    axis: str = "data",
) -> ShardedIndex:
    """Shard points over `axis`, build one local tree per shard."""
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    spec = spec or TreeSpec.ballstar()
    n = points.shape[0]
    per = n // n_shards
    trees, offsets = [], []
    for s in range(n_shards):
        lo = s * per
        hi = n if s == n_shards - 1 else lo + per
        trees.append(build_jax.build(points[lo:hi], spec))
        offsets.append(lo)
    # pad per-shard trees to a common size so leaves stack
    stacked = _stack_trees(trees)
    stack_size = max(int(t.leaf_depths().max()) for t in trees) + 3
    return ShardedIndex(
        mesh=mesh,
        trees=trees,
        stacked=stacked,
        stack_size=stack_size,
        shard_offsets=np.asarray(offsets, np.int64),
    )


def _pad_to(a: np.ndarray, n: int, fill=0.0) -> np.ndarray:
    pad = [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, pad, constant_values=fill)


def _stack_trees(trees: List[Tree]) -> sj.DeviceTree:
    n_nodes = max(t.n_nodes for t in trees)
    n_leaves = max(t.n_leaves for t in trees)
    cap = max(t.leaf_capacity for t in trees)
    d = trees[0].dim

    def prep(t: Tree):
        lp = np.zeros((n_leaves, cap, d), np.float32)
        lp[: t.n_leaves, : t.leaf_capacity] = t.leaf_points
        li = np.full((n_leaves, cap), -1, np.int32)
        li[: t.n_leaves, : t.leaf_capacity] = t.leaf_index
        return sj.DeviceTree(
            center=_pad_to(np.asarray(t.center, np.float32), n_nodes, 1e30),
            radius=_pad_to(np.asarray(t.radius, np.float32), n_nodes, 0.0),
            child_l=_pad_to(np.asarray(t.child_l), n_nodes, -1),
            child_r=_pad_to(np.asarray(t.child_r), n_nodes, -1),
            leaf_of_node=_pad_to(np.asarray(t.leaf_of_node), n_nodes, -1),
            leaf_points=lp,
            leaf_index=li,
        )

    parts = [prep(t) for t in trees]
    return sj.DeviceTree(
        *[
            jnp.stack([np.asarray(getattr(p, f)) for p in parts])
            for f in sj.DeviceTree._fields
        ]
    )


def constrained_knn(
    index: ShardedIndex,
    queries: np.ndarray,  # (Q, d)
    k: int,
    r: float,
    axis: str = "data",
):
    """Exact global constrained-KNN via shard-local search + all_gather
    merge. Returns (global indices (Q, k), distances (Q, k))."""
    mesh = index.mesh
    n_shards = index.n_shards
    q = jnp.asarray(queries, jnp.float32)
    offsets = jnp.asarray(index.shard_offsets, jnp.int32)

    tree_specs = sj.DeviceTree(
        *[P(axis, *([None] * (getattr(index.stacked, f).ndim - 1)))
          for f in sj.DeviceTree._fields]
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(tree_specs, P(), P(axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def search(dt, qs, off):
        # shard-local tree: drop the leading (length-1) shard dim
        local = sj.DeviceTree(*[x[0] for x in dt])
        res = sj.constrained_knn(local, qs, r, k, index.stack_size)
        gids = jnp.where(
            res.indices >= 0, res.indices + off[0], -1
        )  # shard-local -> global ids
        # gather every shard's K-best: (n_shards, Q, k)
        all_d = jax.lax.all_gather(res.distances, axis)
        all_i = jax.lax.all_gather(gids, axis)
        # exact merge: each shard's k-best is already ascending-sorted,
        # so fold them with the unified sorted-merge primitive (no
        # argsort of the n_shards*k concatenation)
        return qmerge.merge_parts(
            [(all_d[s], all_i[s]) for s in range(n_shards)], k
        )

    dist, idx = search(index.stacked, q, offsets)
    return np.asarray(idx), np.asarray(dist)


def brute_constrained_knn(
    points: np.ndarray,   # (N, d) — sharded over `axis`
    mesh: Mesh,
    queries: np.ndarray,  # (Q, d) — replicated
    k: int,
    r: float,
    axis: str = "data",
):
    """Distributed brute-force baseline: no tree at all. Each shard
    streams its point slice once through the fused top-k kernel
    (`search_jax.brute_topk`) and the global K-best is the same
    all_gather + sorted-merge epilogue as the tree path. This is the
    referent the sharded index's speedup is measured against; its HBM
    cost per shard is one read of the slice plus O(Q·k) — the (Q, N)
    distance matrix of the old brute path never exists.

    Returns (global indices (Q, k), distances (Q, k))."""
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    n = points.shape[0]
    per = (n + n_shards - 1) // n_shards
    npad = per * n_shards
    # pad the point set to an even split; padded slots carry gid -1 so
    # the in-kernel liveness mask drops them
    pts = np.zeros((npad, points.shape[1]), np.float32)
    pts[:n] = points
    gids = np.full(npad, -1, np.int32)
    gids[:n] = np.arange(n, dtype=np.int32)
    q = jnp.asarray(queries, jnp.float32)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def scan(p_local, g_local, qs):
        res = sj.brute_topk(p_local, qs, k, r, gids=g_local)
        all_d = jax.lax.all_gather(res.distances, axis)
        all_i = jax.lax.all_gather(res.indices, axis)
        return qmerge.merge_parts(
            [(all_d[s], all_i[s]) for s in range(n_shards)], k
        )

    dist, idx = scan(jnp.asarray(pts), jnp.asarray(gids), q)
    return np.asarray(idx), np.asarray(dist)
