"""Level-synchronous vectorized tree construction (the TPU-native
adaptation of the paper's Algorithm 1 — see DESIGN.md §3).

Instead of per-node recursion (which does not map to TPUs), every level of
the tree is split in one vectorized pass over all N points:

  1. per-segment PCA direction by power iteration (`segment_sum` reductions)
  2. projection t = (x - mean[seg]) · w[seg]
  3. the paper's F(t_c) candidate scan as one (N, S) broadcast + segment
     reduction
  4. side bits -> new implicit node ids (complete-tree numbering 2i+1/2i+2)

Splitting all three tree families (ball*, ball, kd) shares this machinery;
only the axis/threshold selection differs — exactly the same composition as
the host reference builder, which this module is validated against.

The final tree is compacted into the shared `Tree` array-of-structs layout.
Everything up to compaction is jnp; compaction is a small host pass over
the O(n_nodes) node table.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .types import Tree, TreeSpec, leaf_capacity_for

# conservative outward rounding of every stored node/leaf radius (one
# f32 ulp-scale widen, same constant as build_host._R_WIDEN and the
# kernels' r²-slack): the invariant `radius >= max ||p - center||`
# must hold with margin even after the radius re-enters f32 pruning
# arithmetic, and — under quantized leaf storage — after coordinates
# round to bf16/int8 at seal. Widening only ever ADMITS more nodes
# (D_N = |q-c| - r shrinks), so pruning stays sound; exactness of
# results is untouched (the leaf evaluation rescores in f32).
_R_WIDEN = np.float32(1.0 + 2.0**-20)


def _segment_stats(x, seg, weights, num_segs):
    """Per-segment count, mean, radius (max distance to mean),
    conservatively rounded outward by `_R_WIDEN`."""
    w = weights.astype(x.dtype)
    cnt = jax.ops.segment_sum(w, seg, num_segments=num_segs)
    sx = jax.ops.segment_sum(x * w[:, None], seg, num_segments=num_segs)
    mean = sx / jnp.maximum(cnt, 1.0)[:, None]
    d2 = ((x - mean[seg]) ** 2).sum(-1) * w
    r2 = jax.ops.segment_max(
        jnp.where(weights, d2, -jnp.inf), seg, num_segments=num_segs
    )
    radius = jnp.sqrt(jnp.maximum(r2, 0.0)) * _R_WIDEN
    return cnt, mean, radius


def _pca_direction(xc, seg, weights, num_segs, d, iters):
    """Per-segment first principal component via power iteration."""
    # deterministic, identical start for every segment (matches host)
    rng = np.random.default_rng(0)
    v0 = rng.standard_normal(d)
    v0 /= np.linalg.norm(v0)
    w = jnp.broadcast_to(jnp.asarray(v0, xc.dtype), (num_segs, d))
    wmask = weights.astype(xc.dtype)[:, None]

    def body(_, w):
        proj = (xc * w[seg]).sum(-1)[:, None] * wmask
        v = jax.ops.segment_sum(xc * proj, seg, num_segments=num_segs)
        nrm = jnp.linalg.norm(v, axis=-1, keepdims=True)
        return jnp.where(nrm > 1e-12, v / jnp.maximum(nrm, 1e-30), w)

    return jax.lax.fori_loop(0, iters, body, w)


def _ball_axis(x, seg, weights, mean, num_segs):
    """Moore's two-farthest-pivot axis, per segment (baseline ball-tree)."""
    n = x.shape[0]
    ids = jnp.arange(n)

    def seg_argmax(score):
        s = jnp.where(weights, score, -jnp.inf)
        m = jax.ops.segment_max(s, seg, num_segments=num_segs)
        is_max = weights & (s >= m[seg] - 0.0)
        cand = jnp.where(is_max, ids, n)
        return jax.ops.segment_min(cand, seg, num_segments=num_segs)

    i_l = seg_argmax(((x - mean[seg]) ** 2).sum(-1))
    p_l = x[jnp.clip(i_l, 0, n - 1)]
    i_r = seg_argmax(((x - p_l[seg]) ** 2).sum(-1))
    p_r = x[jnp.clip(i_r, 0, n - 1)]
    axis = p_r - p_l
    nrm = jnp.linalg.norm(axis, axis=-1, keepdims=True)
    axis = jnp.where(nrm > 1e-12, axis / jnp.maximum(nrm, 1e-30), 0.0)
    t_pivotmid = ((0.5 * (p_l + p_r)) * axis).sum(-1)
    return axis, t_pivotmid


def _kd_axis(x, seg, weights, num_segs, d):
    """Max-spread coordinate axis, per segment (KD baseline)."""
    big = jnp.where(weights[:, None], x, -jnp.inf)
    small = jnp.where(weights[:, None], x, jnp.inf)
    mx = jax.ops.segment_max(big, seg, num_segments=num_segs)
    mn = -jax.ops.segment_max(-small, seg, num_segments=num_segs)
    dim = jnp.argmax(mx - mn, axis=-1)
    return jax.nn.one_hot(dim, d, dtype=x.dtype)


def _fscan_threshold(t, seg, weights, cnt, num_segs, spec: TreeSpec):
    """Vectorized F(t_c) scan (paper Algorithm 1 line 6) per segment."""
    S = spec.n_candidates
    inf = jnp.inf
    t_hi = jax.ops.segment_max(
        jnp.where(weights, t, -inf), seg, num_segments=num_segs
    )
    t_lo = -jax.ops.segment_max(
        jnp.where(weights, -t, -inf), seg, num_segments=num_segs
    )
    rng = t_hi - t_lo
    frac = (jnp.arange(S, dtype=t.dtype) + 0.5) / S
    cands = t_lo[:, None] + frac[None, :] * rng[:, None]  # (num_segs, S)
    below = (t[:, None] < cands[seg]) & weights[:, None]  # (N, S)
    n1 = jax.ops.segment_sum(
        below.astype(t.dtype), seg, num_segments=num_segs
    )
    n = cnt[:, None]
    f1 = jnp.abs(n - 2.0 * n1) / jnp.maximum(n, 1.0)
    safe_rng = jnp.maximum(rng, 1e-30)[:, None]
    if spec.f2 == "paper":
        f2 = (cands - t_lo[:, None]) / safe_rng
    else:
        mid = 0.5 * (t_lo + t_hi)
        f2 = jnp.abs(cands - mid[:, None]) / safe_rng
    alpha = spec.alpha if spec.threshold == "fscan" else 0.0
    f = f1 + alpha * f2
    choice = jnp.argmin(f, axis=-1)
    t_c = jnp.take_along_axis(cands, choice[:, None], axis=-1)[:, 0]
    if spec.threshold == "mid":  # ablation: plain midpoint cut
        t_c = 0.5 * (t_lo + t_hi)
    return t_c, rng


@functools.partial(jax.jit, static_argnames=("num_segs", "spec_fields"))
def _level(x, point_node, frozen, *, num_segs: int, spec_fields: tuple):
    """Split every node of one level in one compiled program: returns the
    level's per-node stats and the descended (point_node, frozen).
    `spec_fields` is the TreeSpec as a hashable tuple (a jit static)."""
    spec = TreeSpec(*spec_fields)
    d = x.shape[1]
    seg = point_node - (num_segs - 1)
    in_level = ~frozen & (seg >= 0) & (seg < num_segs)
    seg = jnp.where(in_level, seg, 0)

    cnt, mean, radius = _segment_stats(x, seg, in_level, num_segs)
    exists = cnt > 0

    # --- choose axis ---------------------------------------------------
    xc = jnp.where(in_level[:, None], x - mean[seg], 0.0)
    if spec.splitter == "ballstar":
        axis = _pca_direction(xc, seg, in_level, num_segs, d, spec.power_iters)
        t = (xc * axis[seg]).sum(-1)
    elif spec.splitter == "ball":
        axis, t_pivotmid = _ball_axis(x, seg, in_level, mean, num_segs)
        t = (x * axis[seg]).sum(-1)
    elif spec.splitter == "kd":
        axis = _kd_axis(x, seg, in_level, num_segs, d)
        t = (x * axis[seg]).sum(-1)
    else:
        raise ValueError(spec.splitter)

    # --- choose threshold ----------------------------------------------
    t_c, t_range = _fscan_threshold(t, seg, in_level, cnt, num_segs, spec)
    if spec.splitter == "ball":
        t_c = t_pivotmid

    splittable = exists & (cnt > spec.leaf_size) & (t_range > 1e-7)

    # fscan candidates always leave both sides non-empty when range>0;
    # the pivot-midpoint cut can not (pivots are extreme points). Guard
    # anyway: degenerate splits freeze the node as a leaf.
    right = (t < t_c[seg]) & in_level & splittable[seg]
    n_right = jax.ops.segment_sum(
        right.astype(jnp.int32), seg, num_segments=num_segs
    )
    ok = splittable & (n_right > 0) & (n_right < cnt)

    # --- descend ---------------------------------------------------------
    do_split = ok[seg] & in_level
    child = 2 * point_node + 1 + right.astype(jnp.int32)
    point_node = jnp.where(do_split, child, point_node)
    frozen = frozen | (in_level & ~do_split)
    return cnt, mean, radius, exists, ok, point_node, frozen


def build(points: np.ndarray, spec: TreeSpec | None = None) -> Tree:
    """Vectorized construction. Returns the same `Tree` layout as
    `build_host.build` (numpy arrays, ready for `search_jax.device_tree`)."""
    spec = spec or TreeSpec()
    pts = np.asarray(points, np.float32)
    n, d = pts.shape
    max_levels = max(1, int(math.ceil(math.log2(max(2, n)))) + 2)
    # rows padded to a power of two and frozen from the start: they never
    # join a level, so every build of the same pow2 size class reuses one
    # compiled program per level (seals and merges hit the jit cache)
    n_pad = 1 << max(0, (n - 1).bit_length())
    x = jnp.asarray(np.pad(pts, ((0, n_pad - n), (0, 0))))
    point_node = jnp.zeros(n_pad, dtype=jnp.int32)  # implicit complete-tree id
    frozen = jnp.asarray(np.arange(n_pad) >= n)

    # node table accumulated on host: implicit_id -> (center, radius, count,
    # is_leaf). Each level is one compiled `_level` program.
    node_center: Dict[int, np.ndarray] = {}
    node_radius: Dict[int, float] = {}
    node_count: Dict[int, int] = {}
    node_is_leaf: Dict[int, bool] = {}

    for level in range(max_levels):
        base = (1 << level) - 1
        cnt, mean, radius, exists, ok, point_node, frozen = _level(
            x, point_node, frozen, num_segs=1 << level,
            spec_fields=dataclasses.astuple(spec),
        )

        # --- record this level's nodes (host) -------------------------------
        cnt_h = np.asarray(cnt, dtype=np.int64)
        ok_h = np.asarray(ok)
        exists_h = np.asarray(exists)
        mean_h = np.asarray(mean)
        radius_h = np.asarray(radius)
        for j in np.where(exists_h)[0]:
            nid = base + int(j)
            node_center[nid] = mean_h[j]
            node_radius[nid] = float(radius_h[j])
            node_count[nid] = int(cnt_h[j])
            node_is_leaf[nid] = not bool(ok_h[j])

        if not ok_h.any():
            break

    # any node never split at loop end is a leaf (already marked)

    # --- compact into dense BFS arrays (host, O(n_nodes)) -------------------
    implicit_ids = sorted(node_center.keys())
    dense_of = {nid: i for i, nid in enumerate(implicit_ids)}
    n_nodes = len(implicit_ids)
    center = np.stack([node_center[i] for i in implicit_ids])
    radius_arr = np.asarray([node_radius[i] for i in implicit_ids])
    count = np.asarray([node_count[i] for i in implicit_ids], dtype=np.int32)
    child_l = np.full(n_nodes, -1, dtype=np.int32)
    child_r = np.full(n_nodes, -1, dtype=np.int32)
    for nid in implicit_ids:
        if not node_is_leaf[nid]:
            child_l[dense_of[nid]] = dense_of[2 * nid + 1]
            child_r[dense_of[nid]] = dense_of[2 * nid + 2]

    # point ordering: sort by the leaf's slot interval in the complete tree
    # so every node's points are contiguous and nested.
    pn = np.asarray(point_node)[:n]
    max_level_of = np.asarray(
        [int(math.floor(math.log2(i + 1))) for i in implicit_ids]
    )
    deepest = int(max_level_of.max())
    level_of_leaf = np.floor(np.log2(pn + 1)).astype(np.int64)
    local = pn + 1 - (1 << level_of_leaf)
    slot = local << (deepest - level_of_leaf)
    order = np.argsort(slot, kind="stable").astype(np.int64)
    reordered = pts[order]

    # starts: parent-before-children pass over implicit ids (sorted order
    # guarantees parents precede children).
    start = np.zeros(n_nodes, dtype=np.int32)
    for nid in implicit_ids:
        i = dense_of[nid]
        if child_l[i] >= 0:
            l, r = child_l[i], child_r[i]
            start[l] = start[i]
            start[r] = start[i] + count[l]

    # --- padded leaf buckets --------------------------------------------------
    leaf_nodes = np.where(child_l < 0)[0]
    n_leaves = leaf_nodes.shape[0]
    cap = max(
        leaf_capacity_for(spec.leaf_size),
        int(count[leaf_nodes].max()) if n_leaves else 1,
    )
    leaf_points = np.zeros((n_leaves, cap, d), dtype=reordered.dtype)
    leaf_index = np.full((n_leaves, cap), -1, dtype=np.int32)
    leaf_of_node = np.full(n_nodes, -1, dtype=np.int32)
    for rank, node in enumerate(leaf_nodes):
        lo, c = int(start[node]), int(count[node])
        leaf_of_node[node] = rank
        leaf_points[rank, :c] = reordered[lo : lo + c]
        leaf_index[rank, :c] = order[lo : lo + c]

    return Tree(
        center=center,
        radius=radius_arr,
        child_l=child_l,
        child_r=child_r,
        start=start,
        count=count,
        points=reordered,
        perm=order,
        leaf_of_node=leaf_of_node,
        leaf_points=leaf_points,
        leaf_index=leaf_index,
        spec=spec,
    )
