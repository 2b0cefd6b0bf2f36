"""Batched, jit-compiled tree search — the production TPU path.

Queries are vmapped over an explicit-stack `lax.while_loop` traversal of
the array-of-structs tree. The traversal order, pruning rules and node
accounting replicate the host reference (`search_host`) exactly:

  pop nearest-first DFS;  D_N = max(D_parent, |q-c| - radius);
  prune when D_N >= D_s (KNN) OR D_N > r (range);
  children pushed only if their ball intersects the range ball.

`knn` is `constrained_knn` with r = inf (the range gates become no-ops),
exactly as in the paper where constrained NN degenerates to Liu et al.'s
algorithm for unbounded range.

Note the DFS stack bound: each pop removes one entry and pushes at most
two, and expansion only descends, so the stack never exceeds depth+2.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

import repro.query.merge as qmerge
from repro.kernels import topk_l2 as _tk

from .types import Tree


class DeviceTree(NamedTuple):
    center: jax.Array      # (n_nodes, d)
    radius: jax.Array      # (n_nodes,)
    child_l: jax.Array     # (n_nodes,)
    child_r: jax.Array     # (n_nodes,)
    leaf_of_node: jax.Array  # (n_nodes,)
    leaf_points: jax.Array   # (n_leaves, cap, d)
    leaf_index: jax.Array    # (n_leaves, cap)


def device_tree(tree: Tree, dtype=jnp.float32) -> DeviceTree:
    return DeviceTree(
        center=jnp.asarray(np.asarray(tree.center), dtype),
        radius=jnp.asarray(np.asarray(tree.radius), dtype),
        child_l=jnp.asarray(np.asarray(tree.child_l), jnp.int32),
        child_r=jnp.asarray(np.asarray(tree.child_r), jnp.int32),
        leaf_of_node=jnp.asarray(np.asarray(tree.leaf_of_node), jnp.int32),
        leaf_points=jnp.asarray(np.asarray(tree.leaf_points), dtype),
        leaf_index=jnp.asarray(np.asarray(tree.leaf_index), jnp.int32),
    )


def max_depth(tree: Tree) -> int:
    return int(tree.leaf_depths().max())


class KnnResult(NamedTuple):
    indices: jax.Array    # (Q, k) original point ids, -1 = no result
    distances: jax.Array  # (Q, k) inf where no result
    nodes_visited: jax.Array  # (Q,)
    # paper-metric accounting (host oracle parity: SearchStats fields).
    # `leaves_visited` counts scanned NON-EMPTY leaves (a leaf whose
    # slots are all -1 — only the stacked batch's dummy pad member has
    # one — is not billed); `points_examined` counts live leaf slots
    # whose distance was evaluated (the paper's "distance computations")
    leaves_visited: Optional[jax.Array] = None    # (Q,)
    points_examined: Optional[jax.Array] = None   # (Q,)


def _leaf_sq(pts, q):
    """Squared distances of a leaf's points to the query, computed over
    the feature dim zero-padded to the kernel's 128-lane width. The
    padding lanes are exact no-ops, but they pin the REDUCTION SHAPE:
    for tiny d (e.g. d=2) XLA otherwise contracts the sum into an FMA
    with different rounding than `leaf_topk_l2`'s in-kernel Σ(q-c)²,
    breaking the fused path's bit-parity with this loop."""
    d = pts.shape[-1]
    dp = -(-d // 128) * 128
    if dp != d:
        pts = jnp.pad(pts, [(0, 0)] * (pts.ndim - 1) + [(0, dp - d)])
        q = jnp.pad(q, (0, dp - d))
    return ((pts - q) ** 2).sum(-1)


def _traverse_one(dt: DeviceTree, q, r, k: int, stack_size: int):
    """Single-query constrained-KNN traversal (vmapped by callers).

    Leaf evaluation runs entirely in SQUARED distances: the per-leaf
    full-width `sqrt` the old path paid on every visited leaf is gone —
    the only sqrt inside the loop is one scalar per iteration, turning
    the carried k-th best back into the euclidean `d_s` the node-level
    pruning (and the host oracle) compares against. The radius gate
    uses the conservatively-squared `radius_sq_upper(r)` in-loop and is
    refined exactly (`sqrt(sq) <= r`) on the k survivors after the
    loop; conservative false admits rank strictly after every true
    candidate in the squared domain, so they only ever occupy trailing
    slots and the refinement removes them without reordering anything
    (see `kernels/topk_l2.py` for the full argument).
    """
    inf = jnp.asarray(jnp.inf, dt.center.dtype)
    r2 = _tk.radius_sq_upper(r)

    stack_n = jnp.zeros(stack_size, jnp.int32)
    stack_b = jnp.zeros(stack_size, dt.center.dtype)
    best_sq = jnp.full((k,), inf, dt.center.dtype)
    best_i = jnp.full((k,), -1, jnp.int32)

    def cond(state):
        sp, *_ = state
        return sp > 0

    def body(state):
        sp, stack_n, stack_b, best_sq, best_i, visits, leaves, cands = state
        sp = sp - 1
        node = stack_n[sp]
        d_par = stack_b[sp]
        visits = visits + 1

        dc = jnp.linalg.norm(q - dt.center[node])
        d_n = jnp.maximum(d_par, dc - dt.radius[node])
        # one scalar sqrt recovers the euclidean k-th best: node pruning
        # stays in the euclidean domain, bit-identical to the host oracle
        d_s = jnp.sqrt(best_sq[k - 1])
        prune = (d_n >= d_s) | (d_n > r)
        is_leaf = dt.child_l[node] < 0

        # ---- leaf evaluation (masked; discarded unless leaf & !prune) ----
        # `best` is kept ascending-sorted, so the update is the unified
        # merge primitive (leaf top-k, then a sorted two-way merge) —
        # no argsort of the (k + cap)-wide concatenation
        rank = jnp.maximum(dt.leaf_of_node[node], 0)
        pts = dt.leaf_points[rank]            # (cap, d)
        li = dt.leaf_index[rank]              # (cap,)
        sql = jnp.maximum(_leaf_sq(pts, q), 0.0)
        ok = (li >= 0) & (sql <= r2) & (sql < best_sq[k - 1])
        sql = jnp.where(ok, sql, inf)
        li = jnp.where(ok, li, -1)
        ld, lidx = qmerge.topk_sorted(sql, li, k)
        new_sq, new_i = qmerge.merge_sorted(best_sq, best_i, ld, lidx)
        new_sq, new_i = new_sq[:k], new_i[:k]
        take_leaf = is_leaf & ~prune
        best_sq = jnp.where(take_leaf, new_sq, best_sq)
        best_i = jnp.where(take_leaf, new_i, best_i)
        # paper accounting, host-oracle parity: leaves_visited counts a
        # scanned leaf holding at least one live point (so the stacked
        # dummy pad member — an all-dead leaf — bills nothing), and
        # points_examined counts the live slots whose distance was
        # computed (dead/padding slots are masked, never candidates)
        n_real = (dt.leaf_index[rank] >= 0).sum().astype(jnp.int32)
        leaves = leaves + jnp.where(take_leaf & (n_real > 0), 1, 0)
        cands = cands + jnp.where(take_leaf, n_real, 0)

        # ---- internal expansion ------------------------------------------
        l = jnp.maximum(dt.child_l[node], 0)
        rr = jnp.maximum(dt.child_r[node], 0)
        dcl = jnp.linalg.norm(q - dt.center[l])
        dcr = jnp.linalg.norm(q - dt.center[rr])
        near, far = (
            jnp.where(dcl <= dcr, l, rr),
            jnp.where(dcl <= dcr, rr, l),
        )
        d_near = jnp.minimum(dcl, dcr)
        d_far = jnp.maximum(dcl, dcr)
        gate_near = d_near <= dt.radius[near] + r
        gate_far = d_far <= dt.radius[far] + r
        expand = ~is_leaf & ~prune
        push_far = (expand & gate_far).astype(jnp.int32)
        push_near = (expand & gate_near).astype(jnp.int32)
        # push farther first so the nearer child is popped first
        stack_n = stack_n.at[sp].set(
            jnp.where(push_far == 1, far, stack_n[sp])
        )
        stack_b = stack_b.at[sp].set(
            jnp.where(push_far == 1, d_n, stack_b[sp])
        )
        sp1 = sp + push_far
        idx1 = jnp.minimum(sp1, stack_size - 1)
        stack_n = stack_n.at[idx1].set(
            jnp.where(push_near == 1, near, stack_n[idx1])
        )
        stack_b = stack_b.at[idx1].set(
            jnp.where(push_near == 1, d_n, stack_b[idx1])
        )
        sp2 = sp1 + push_near
        return (sp2, stack_n, stack_b, best_sq, best_i, visits, leaves, cands)

    state = (
        jnp.int32(1),
        stack_n,
        stack_b,
        best_sq,
        best_i,
        jnp.int32(0),
        jnp.int32(0),
        jnp.int32(0),
    )
    (sp, _, _, best_sq, best_i, visits, leaves, cands) = jax.lax.while_loop(
        cond, body, state
    )
    # exact radius refinement: sqrt only the k survivors, drop the
    # (trailing) conservative false admits
    best_d = jnp.sqrt(best_sq)
    okf = best_d <= r
    best_d = jnp.where(okf, best_d, inf)
    best_i = jnp.where(okf, best_i, -1)
    return best_d, best_i, visits, leaves, cands


@functools.partial(jax.jit, static_argnames=("k", "stack_size"))
def constrained_knn(
    dt: DeviceTree,
    queries: jax.Array,   # (Q, d)
    r,                    # scalar or (Q,)
    k: int,
    stack_size: int,
) -> KnnResult:
    r = jnp.broadcast_to(jnp.asarray(r, dt.center.dtype), queries.shape[:1])
    fn = jax.vmap(
        lambda q, ri: _traverse_one(dt, q, ri, k, stack_size)
    )
    best_d, best_i, visits, leaves, cands = fn(queries, r)
    return KnnResult(
        indices=best_i,
        distances=best_d,
        nodes_visited=visits,
        leaves_visited=leaves,
        points_examined=cands,
    )


@functools.partial(jax.jit, static_argnames=("k", "stack_size"))
def knn(dt: DeviceTree, queries: jax.Array, k: int, stack_size: int):
    r = jnp.full(queries.shape[:1], jnp.inf, dt.center.dtype)
    fn = jax.vmap(lambda q, ri: _traverse_one(dt, q, ri, k, stack_size))
    best_d, best_i, visits, leaves, cands = fn(queries, r)
    return KnnResult(
        indices=best_i,
        distances=best_d,
        nodes_visited=visits,
        leaves_visited=leaves,
        points_examined=cands,
    )


class StackedResult(NamedTuple):
    gids: jax.Array           # (Q, k) merged global ids, -1 = no result
    distances: jax.Array      # (Q, k) merged, ascending; inf = no result
    nodes_visited: jax.Array  # (Q,) summed over the stacked segments
    leaves_visited: Optional[jax.Array] = None    # (Q,) summed, non-empty
    points_examined: Optional[jax.Array] = None   # (Q,) summed live slots


@functools.partial(jax.jit, static_argnames=("k", "stack_size"))
def constrained_knn_stacked(
    dts: DeviceTree,      # (S, …)-stacked same-shape-class segments
    gids: jax.Array,      # (S, n) i32 local id -> global id, -1 padding
    queries: jax.Array,   # (Q, d)
    r,                    # scalar or (Q,)
    k: int,
    stack_size: int,
) -> StackedResult:
    """All S same-shape segments in ONE device dispatch: vmap the
    traversal over the stacked segment axis, map local hits to global
    ids on device, and fold the S sorted k-bests with the unified merge
    — the answer leaves the device already merged."""
    r = jnp.broadcast_to(jnp.asarray(r, dts.center.dtype), queries.shape[:1])
    n = gids.shape[1]

    def per_segment(dt, g):
        bd, bi, v, lv, pe = jax.vmap(
            lambda q, ri: _traverse_one(dt, q, ri, k, stack_size)
        )(queries, r)
        gg = jnp.where(bi >= 0, g[jnp.clip(bi, 0, n - 1)], -1)
        return bd, gg, v, lv, pe

    bd, gg, v, lv, pe = jax.vmap(per_segment)(dts, gids)  # (S, Q, …)
    d, g = qmerge.merge_parts([(bd[s], gg[s]) for s in range(bd.shape[0])], k)
    return StackedResult(
        gids=g,
        distances=d,
        nodes_visited=v.sum(0),
        leaves_visited=lv.sum(0),
        points_examined=pe.sum(0),
    )


# ---------------------------------------------------------------------------
# Two-phase fused traversal: collect the leaf frontier with the same
# while_loop pruning (phase 1), then evaluate every surviving leaf's
# candidates in ONE batched Pallas kernel launch (phase 2). Exactness:
# the classic traversal's incremental k-best equals the global top-k of
# all evaluated-leaf candidates keyed by (squared distance, DFS
# insertion order) — the exact key `leaf_topk_l2` selects on — so both
# paths produce bit-identical results AND bit-identical paper-metric
# counts (phase 1 runs the same pruning, so it visits the same nodes).
# ---------------------------------------------------------------------------

FRONTIER_CAP_DEFAULT = 64


def frontier_cap_default() -> int:
    """Static per-query leaf-frontier capacity of the fused path
    (`REPRO_FRONTIER_CAP` overrides). Queries whose pruned frontier
    exceeds it fall back to the classic in-loop evaluation — exact
    either way, the cap only bounds the phase-2 gather footprint."""
    return int(os.environ.get("REPRO_FRONTIER_CAP", FRONTIER_CAP_DEFAULT))


def _collect_one(dt: DeviceTree, q, r, k: int, stack_size: int, fcap: int):
    """Phase 1: the `_traverse_one` loop with the SAME pruning state
    evolution (squared k-best values, one scalar sqrt per iteration)
    but no id bookkeeping — instead it records the rank of every
    scanned non-empty leaf, in DFS visit order, into a (fcap,) list.
    `nf` keeps counting past the cap so the caller can detect
    truncation and fall back."""
    inf = jnp.asarray(jnp.inf, dt.center.dtype)
    r2 = _tk.radius_sq_upper(r)

    stack_n = jnp.zeros(stack_size, jnp.int32)
    stack_b = jnp.zeros(stack_size, dt.center.dtype)
    best_sq = jnp.full((k,), inf, dt.center.dtype)
    frontier = jnp.full((fcap,), -1, jnp.int32)

    def cond(state):
        sp, *_ = state
        return sp > 0

    def body(state):
        (sp, stack_n, stack_b, best_sq, frontier, nf,
         visits, leaves, cands) = state
        sp = sp - 1
        node = stack_n[sp]
        d_par = stack_b[sp]
        visits = visits + 1

        dc = jnp.linalg.norm(q - dt.center[node])
        d_n = jnp.maximum(d_par, dc - dt.radius[node])
        d_s = jnp.sqrt(best_sq[k - 1])
        prune = (d_n >= d_s) | (d_n > r)
        is_leaf = dt.child_l[node] < 0

        # ---- leaf evaluation: values only (d_s parity, no ids) ----------
        rank = jnp.maximum(dt.leaf_of_node[node], 0)
        pts = dt.leaf_points[rank]            # (cap, d)
        li = dt.leaf_index[rank]              # (cap,)
        sql = jnp.maximum(_leaf_sq(pts, q), 0.0)
        ok = (li >= 0) & (sql <= r2) & (sql < best_sq[k - 1])
        sql = jnp.where(ok, sql, inf)
        ld = qmerge.topk_vals(sql, k)
        new_sq = qmerge.merge_sorted_vals(best_sq, ld)[:k]
        take_leaf = is_leaf & ~prune
        best_sq = jnp.where(take_leaf, new_sq, best_sq)

        # paper accounting: identical to `_traverse_one`
        n_real = (dt.leaf_index[rank] >= 0).sum().astype(jnp.int32)
        leaves = leaves + jnp.where(take_leaf & (n_real > 0), 1, 0)
        cands = cands + jnp.where(take_leaf, n_real, 0)

        # ---- frontier recording (empty leaves contribute nothing) -------
        record = take_leaf & (n_real > 0)
        widx = jnp.minimum(nf, fcap - 1)
        frontier = frontier.at[widx].set(
            jnp.where(record & (nf < fcap), rank, frontier[widx])
        )
        nf = nf + jnp.where(record, 1, 0)

        # ---- internal expansion (identical to `_traverse_one`) ----------
        l = jnp.maximum(dt.child_l[node], 0)
        rr = jnp.maximum(dt.child_r[node], 0)
        dcl = jnp.linalg.norm(q - dt.center[l])
        dcr = jnp.linalg.norm(q - dt.center[rr])
        near, far = (
            jnp.where(dcl <= dcr, l, rr),
            jnp.where(dcl <= dcr, rr, l),
        )
        d_near = jnp.minimum(dcl, dcr)
        d_far = jnp.maximum(dcl, dcr)
        gate_near = d_near <= dt.radius[near] + r
        gate_far = d_far <= dt.radius[far] + r
        expand = ~is_leaf & ~prune
        push_far = (expand & gate_far).astype(jnp.int32)
        push_near = (expand & gate_near).astype(jnp.int32)
        stack_n = stack_n.at[sp].set(
            jnp.where(push_far == 1, far, stack_n[sp])
        )
        stack_b = stack_b.at[sp].set(
            jnp.where(push_far == 1, d_n, stack_b[sp])
        )
        sp1 = sp + push_far
        idx1 = jnp.minimum(sp1, stack_size - 1)
        stack_n = stack_n.at[idx1].set(
            jnp.where(push_near == 1, near, stack_n[idx1])
        )
        stack_b = stack_b.at[idx1].set(
            jnp.where(push_near == 1, d_n, stack_b[idx1])
        )
        sp2 = sp1 + push_near
        return (sp2, stack_n, stack_b, best_sq, frontier, nf,
                visits, leaves, cands)

    state = (
        jnp.int32(1), stack_n, stack_b, best_sq, frontier,
        jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.int32(0),
    )
    (_, _, _, _, frontier, nf, visits, leaves, cands) = jax.lax.while_loop(
        cond, body, state
    )
    return frontier, nf, visits, leaves, cands


@functools.partial(
    jax.jit, static_argnames=("k", "stack_size", "frontier_cap")
)
def _collect_frontier_stacked(
    dts: DeviceTree, queries, r, k: int, stack_size: int, frontier_cap: int
):
    """Phase 1 over all S stacked segments × Q queries: per-(s, q)
    frontier leaf ranks (DFS order, -1 padded), true frontier sizes,
    and the classic traversal's paper-metric counts."""
    r = jnp.broadcast_to(jnp.asarray(r, dts.center.dtype), queries.shape[:1])

    def per_segment(dt):
        return jax.vmap(
            lambda q, ri: _collect_one(dt, q, ri, k, stack_size, frontier_cap)
        )(queries, r)

    return jax.vmap(per_segment)(dts)  # each (S, Q, …)


@jax.jit
def _gather_frontier(dts: DeviceTree, gids, queries, r, frontier):
    """Phase 2 gather: pull each (segment, query) row's frontier leaves
    into a private padded candidate matrix, local ids mapped to global
    gids (holes/dead slots → -1)."""
    s, qn, f = frontier.shape
    n = gids.shape[1]

    def per_seg(lp, li, g, fr):
        rc = jnp.clip(fr, 0, lp.shape[0] - 1)       # (Q, F)
        cpts = lp[rc]                                # (Q, F, cap, d)
        cli = li[rc]                                 # (Q, F, cap)
        live = (cli >= 0) & (fr >= 0)[..., None]
        cg = jnp.where(live, g[jnp.clip(cli, 0, n - 1)], -1)
        cap, dim = lp.shape[1], lp.shape[2]
        return (
            cpts.reshape(qn, f * cap, dim),
            cg.reshape(qn, f * cap),
        )

    cpts, cg = jax.vmap(per_seg)(
        dts.leaf_points, dts.leaf_index, gids, frontier
    )
    dim = queries.shape[1]
    qrows = jnp.broadcast_to(queries[None], (s, qn, dim)).reshape(-1, dim)
    rb = jnp.broadcast_to(jnp.asarray(r, queries.dtype), (qn,))
    rrows = jnp.broadcast_to(rb[None], (s, qn)).reshape(-1)
    c = cpts.shape[2]
    return qrows, cpts.reshape(s * qn, c, dim), cg.reshape(s * qn, c), rrows


@jax.jit
def _gather_frontier_quantized(leaf_q, leaf_index, gids, frontier, qscale):
    """Phase-2 gather over the QUANTIZED leaf buffer: same row layout
    as `_gather_frontier` (so candidate slots coincide position-for-
    position with the f32 gather) but the candidate tensor stays in its
    storage dtype, and int8 segments broadcast their per-leaf dequant
    scale to a per-candidate (R, C) f32 row for the kernel."""
    s, qn, f = frontier.shape
    n = gids.shape[1]
    cap, dim = leaf_q.shape[2], leaf_q.shape[3]

    def per_seg(lq, li, g, fr):
        rc = jnp.clip(fr, 0, lq.shape[0] - 1)        # (Q, F)
        cq = lq[rc]                                   # (Q, F, cap, d)
        cli = li[rc]                                  # (Q, F, cap)
        live = (cli >= 0) & (fr >= 0)[..., None]
        cg = jnp.where(live, g[jnp.clip(cli, 0, n - 1)], -1)
        return cq.reshape(qn, f * cap, dim), cg.reshape(qn, f * cap)

    cq, cg = jax.vmap(per_seg)(leaf_q, leaf_index, gids, frontier)
    out_sc = None
    if qscale is not None:

        def per_seg_sc(sc, fr):
            rc = jnp.clip(fr, 0, sc.shape[0] - 1)
            cs = jnp.broadcast_to(sc[rc][..., None], (qn, f, cap))
            return cs.reshape(qn, f * cap)

        out_sc = jax.vmap(per_seg_sc)(qscale, frontier).reshape(
            s * qn, f * cap
        )
    return (
        cq.reshape(s * qn, f * cap, dim),
        cg.reshape(s * qn, f * cap),
        out_sc,
    )


@functools.partial(jax.jit, static_argnames=("k",))
def _rescore_topk(dts: DeviceTree, frontier, queries, r, gq, slots, k: int):
    """Exact second pass of the quantized read path: gather ONLY the k′
    surviving slots' f32 rows, recompute their squared distances with
    the f32 kernel's exact arithmetic (feature dim padded to the
    128-lane block width — see `_leaf_sq`), and select the final top-k
    by the same (squared, slot) lexicographic key under the same
    conservative in-kernel gate + exact euclidean refinement. Given
    candidate-set containment (checked by the caller), the output is
    bit-identical to running `leaf_topk_l2` on the full f32 gather.

    frontier: (S, Q, F) effective frontier; gq/slots: (S·Q, k′) the
    quantized kernel's kept gids/slots. Returns per-row
    ``(distances (S·Q, k), gids (S·Q, k), sorted_sq (S·Q, k′))`` — the
    sorted gated rescored squares ride back out so the caller's
    containment check can read the k-th best exactly as selected."""
    s, qn, f = frontier.shape
    cap = dts.leaf_points.shape[2]
    kprime = slots.shape[1]
    sl = slots.reshape(s, qn, kprime)

    def per_seg(lp, fr, sl_):
        slc = jnp.clip(sl_, 0, f * cap - 1)
        fi = slc // cap                          # frontier position
        pos = slc % cap                          # slot within the leaf
        rank = jnp.take_along_axis(fr, fi, axis=1)
        rank = jnp.clip(rank, 0, lp.shape[0] - 1)
        return lp[rank, pos]                     # (Q, k′, d) f32 rows

    rows = jax.vmap(per_seg)(dts.leaf_points, frontier, sl)  # (S,Q,k′,d)
    d = rows.shape[-1]
    dp = -(-d // 128) * 128
    rows_p = jnp.pad(rows, [(0, 0)] * 3 + [(0, dp - d)])
    q_p = jnp.pad(jnp.asarray(queries, jnp.float32), [(0, 0), (0, dp - d)])
    diff = rows_p - q_p[None, :, None, :]
    sq = jnp.maximum((diff * diff).sum(-1), 0.0)  # (S, Q, k′) exact f32
    sq = sq.reshape(s * qn, kprime)

    rb = jnp.broadcast_to(jnp.asarray(r, jnp.float32), (qn,))
    rrows = jnp.broadcast_to(rb[None], (s, qn)).reshape(-1)  # (S·Q,)
    # the f32 kernel's in-kernel state, reproduced on the survivors:
    # liveness + conservative squared gate, masked lanes (+inf, I32MAX)
    ok = (gq >= 0) & (sq <= _tk.radius_sq_upper(rrows)[:, None])
    skey = jnp.where(ok, sq, jnp.inf)
    slkey = jnp.where(ok, slots, np.iinfo(np.int32).max)
    gkey = jnp.where(ok, gq, -1)
    skey, slkey, gkey = jax.lax.sort(
        (skey, slkey, gkey), dimension=1, num_keys=2
    )
    sq_k = skey[:, :k]
    # exact euclidean refinement — same tail as `leaf_topk_l2`
    dl = jnp.sqrt(sq_k)
    okf = dl <= rrows[:, None]
    dd = jnp.where(okf, dl, jnp.inf)
    gg = jnp.where(okf, gkey[:, :k], -1)
    return dd, gg, skey


@jax.jit
def _frontier_width(nf):
    """[max, sum] of the phase-1 frontier sizes, in one transfer: the
    widest frontier sets phase 2's pow2 width class, the sum counts the
    real leaves phase 2 gathers."""
    return jnp.stack([jnp.max(nf), jnp.sum(nf)])


@functools.partial(jax.jit, static_argnames=("k",))
def _merge_segments(dd, gg, k: int):
    """Fold the S per-segment sorted k-bests — same merge the classic
    stacked path uses, so cross-segment tie-breaks are identical."""
    return qmerge.merge_parts(
        [(dd[s], gg[s]) for s in range(dd.shape[0])], k
    )


QUANT_SLACK_DEFAULT = 8


def quant_slack_default() -> int:
    """Over-fetch slack of the quantized read path: the quantized
    kernel keeps k′ = k + slack survivors so the exact f32 rescore has
    room for quantization-induced rank shuffles near the k-boundary
    (`REPRO_QUANT_SLACK` overrides). Exhausting the slack triggers the
    counted all-f32 fallback — never truncation."""
    return int(os.environ.get("REPRO_QUANT_SLACK", QUANT_SLACK_DEFAULT))


def _quant_contained(sq_q, gq, rescored_sq, qerr: float, dim: int, k: int):
    """Host-side containment certificate of the quantized candidate
    set: True iff every row's exact top-k provably survived the
    quantized k′-selection.

    Per row, candidates the kernel EXCLUDED have quantized squared
    distance >= T = the k′-th kept value (a bitwise fact of the
    in-kernel selection), hence exact distance >= sqrt(T)/m - qerr
    where `m` bounds the f32 evaluation slop of a padded length-`dim`
    Σ(q-c)² and qerr the seal-time dequantization error. If the k-th
    rescored survivor is strictly closer than that (with the same slop
    margin on its own side), no excluded candidate can enter the final
    top-k — even on ties, because exclusion is then STRICTLY farther.
    Rows whose k′-window never filled with live candidates (n_live <
    k′) excluded nothing that passed the widened radius gate, so they
    are trivially contained."""
    sq_q = np.asarray(sq_q)
    gq = np.asarray(gq)
    rs = np.asarray(rescored_sq)
    kprime = sq_q.shape[1]
    n_live = (gq >= 0).sum(axis=1)
    window_open = n_live < kprime
    # margin for f32 evaluation error of the squared-distance sums on
    # BOTH sides of the comparison. Since the int8 dequant product is
    # exact (pow2 scales), the kernel keys are bitwise-deterministic
    # and the only slop left is the sub/square/accumulate roundings of
    # a length-`dim` sum — <= ~(dim+2)*2^-24 relative; dim * 2^-23
    # keeps a 2x cushion (was 2^-22 when fma contraction of the
    # dequant multiply made the keys themselves 1-ulp ambiguous)
    m = 1.0 + max(dim, 1) * 2.0**-23
    t = np.sqrt(np.maximum(sq_q[:, kprime - 1], 0.0))
    s_k = np.sqrt(np.maximum(rs[:, k - 1], 0.0)) if k <= kprime else np.inf
    gap_ok = s_k * m + qerr < t / m
    return bool(np.all(window_open | gap_ok))


def constrained_knn_stacked_fused(
    dts: DeviceTree,
    gids: jax.Array,
    queries: jax.Array,
    r,
    k: int,
    stack_size: int,
    frontier_cap: int | None = None,
    leaf_q: jax.Array | None = None,   # (S, L, cap, d) quantized storage
    qscale: jax.Array | None = None,   # (S, L) f32 int8 per-leaf scales
    qerr: float = 0.0,                 # max seal-time dequant error bound
) -> StackedResult | None:
    """Two-phase fused traversal over S stacked segments: collect the
    pruned leaf frontier (phase 1), evaluate every surviving candidate
    with one `leaf_topk_l2` launch (phase 2), merge across segments on
    device. Bit-identical to `constrained_knn_stacked` — results AND
    nodes/leaves/candidates counts.

    When `leaf_q` is given (bf16, or int8 + `qscale`), phase 2 streams
    the QUANTIZED buffer instead: the kernel over-fetches k′ = k +
    slack survivors by quantized distance under a radius gate widened
    by `qerr`, then `_rescore_topk` recomputes exact f32 distances for
    just those survivors. A per-dispatch containment certificate
    (`_quant_contained`) proves the quantized candidate set ⊇ the true
    top-k; when the slack is exhausted the dispatch re-runs on the f32
    buffer (counted on the registry as `quantized.rescore{result=
    fallback}`) — results are bit-identical to the f32 path either
    way, never truncated. Phase 1 always runs on f32 coordinates, so
    pruning decisions and paper-metric counts are storage-independent.

    Returns None when some query's frontier overflowed `frontier_cap`
    (the recorded list would be truncated): the caller falls back to
    the classic path, which is exact at any frontier size.

    Each step runs in its own `obs.span` (`engine.phase1`,
    `engine.frontier_sync`, `engine.phase2`, `engine.certificate`,
    `engine.f32_rerun`, `engine.segment_merge`), and
    `engine.phase2_slots{kind=frontier|evaluated}` counts the real
    frontier leaves against the padded leaf slots phase 2 evaluates.
    """
    from repro import obs  # lazy: keep core import-light
    from repro.kernels import ops  # lazy: ops pulls in the obs registry

    if frontier_cap is None:
        frontier_cap = frontier_cap_default()
    # every statement below that launches device work or waits for it
    # sits in exactly one child span of the caller's `engine.dispatch`
    with obs.span("engine.phase1"):
        frontier, nf, v, lv, pe = _collect_frontier_stacked(
            dts, queries, r, k, stack_size, frontier_cap
        )
    with obs.span("engine.frontier_sync"):
        # the host waits here for phase 1: one transfer of (max, sum)
        nf_max, nf_sum = (
            (int(x) for x in jax.device_get(_frontier_width(nf)))
            if nf.size
            else (0, 0)
        )
    if nf_max > frontier_cap:
        return None
    # shrink the gather to the smallest pow2 class that holds the
    # widest frontier: bounds phase-2 memory at log2(fcap) jit classes
    f_eff = max(1, min(_tk._next_pow2(max(nf_max, 1)), frontier_cap))
    s, qn = frontier.shape[0], frontier.shape[1]
    # phase-2 fill: real frontier leaves against the leaf slots phase 2
    # evaluates once every row is padded to f_eff (an f32 rerun
    # evaluates the same slots again and is not counted twice)
    obs.REGISTRY.counter("engine.phase2_slots", kind="frontier").inc(nf_sum)
    obs.REGISTRY.counter("engine.phase2_slots", kind="evaluated").inc(
        s * qn * f_eff
    )
    # pin bk to cover the whole feature dim: one k-chunk per block, so
    # the in-kernel Σ(q-c)² accumulates in a single pass — the same
    # rounding as the traversal's in-loop `((pts-q)**2).sum(-1)`. A
    # smaller autotuned bk would split the sum and break bit-parity;
    # bm/bn stay tunable (they never change the arithmetic).
    bk = _tk._round_up(max(int(queries.shape[1]), 1), 128)

    def f32_topk(frontier_eff):
        qrows, cands, cgids, rrows = _gather_frontier(
            dts, gids, queries, r, frontier_eff
        )
        return ops.leaf_topk_l2(qrows, cands, cgids, rrows, k, bk=bk)

    with obs.span("engine.phase2"):
        frontier_eff = frontier[..., :f_eff]
        if leaf_q is None:
            dd, gg = f32_topk(frontier_eff)
        else:
            kprime = k + max(1, quant_slack_default())
            cq, cg, csc = _gather_frontier_quantized(
                leaf_q, dts.leaf_index, gids, frontier_eff, qscale
            )
            rb = jnp.broadcast_to(
                jnp.asarray(r, jnp.float32), queries.shape[:1]
            )
            qrows = jnp.broadcast_to(
                queries[None], (s, qn, queries.shape[1])
            ).reshape(-1, queries.shape[1])
            # widen the euclidean gate by the dequant bound so no true
            # in-radius neighbor can fail the in-kernel quantized gate
            rgate = jnp.broadcast_to(
                (rb + jnp.float32(qerr))[None], (s, qn)
            ).reshape(-1)
            sq_q, gq, slots = ops.leaf_topk_l2_raw(
                qrows, cq, cg, rgate, kprime, cscale=csc, bk=bk
            )
            dd, gg, rescored = _rescore_topk(
                dts, frontier_eff, queries, r, gq, slots, k
            )
    if leaf_q is not None:
        with obs.span("engine.certificate"):
            contained = _quant_contained(
                sq_q, gq, rescored, qerr, queries.shape[1], k
            )
        obs.REGISTRY.counter(
            "quantized.rescore", result="exact" if contained else "fallback"
        ).inc()
        if not contained:
            # slack exhausted: the certificate cannot prove the true
            # top-k survived — re-run this dispatch on the f32 buffer
            # (exact by construction, never truncates)
            with obs.span("engine.f32_rerun"):
                dd, gg = f32_topk(frontier_eff)
    with obs.span("engine.segment_merge"):
        d, g = _merge_segments(
            dd.reshape(s, qn, k), gg.reshape(s, qn, k), k
        )
        return StackedResult(
            gids=g,
            distances=d,
            nodes_visited=v.sum(0),
            leaves_visited=lv.sum(0),
            points_examined=pe.sum(0),
        )


def brute_topk(
    points: jax.Array,    # (N, d)
    queries: jax.Array,   # (Q, d)
    k: int,
    r=jnp.inf,            # scalar or (Q,) euclidean radius
    gids: jax.Array | None = None,  # (N,) ids; default arange(N)
) -> KnnResult:
    """Exact constrained-KNN with NO tree: one fused streaming scan of
    `points` (`kernels/topk_l2.py`). This is the brute referent every
    traversal is validated/benchmarked against, and the per-shard leg
    of the distributed brute baseline — it never materializes a (Q, N)
    distance matrix, so its HBM cost is a single read of `points` plus
    the (Q, k) answer. Results follow the `query/merge` sorted
    convention ((+inf, -1) padding, ties to the lower slot)."""
    from repro.kernels import ops

    p = jnp.asarray(points, jnp.float32)
    q = jnp.asarray(queries, jnp.float32).reshape(-1, p.shape[1])
    g = (
        jnp.arange(p.shape[0], dtype=jnp.int32)
        if gids is None
        else jnp.asarray(gids, jnp.int32)
    )
    d, i = ops.topk_l2(q, p, g, r, k)
    return KnnResult(
        indices=i,
        distances=d,
        nodes_visited=jnp.zeros(q.shape[0], jnp.int32),
    )


def search(
    tree: Tree,
    queries: np.ndarray,
    k: int,
    r: float | np.ndarray = np.inf,
    dtype=jnp.float32,
) -> KnnResult:
    """Convenience wrapper: host tree in, batched search out — a thin
    adapter over the unified query engine (shape-class padded, so a
    static tree shares its compiled traversal with any streaming
    segment of the same class)."""
    from repro.query import engine as qengine  # lazy: engine imports us
    from repro.query.spec import QuerySpec

    return qengine.search_tree(tree, queries, QuerySpec(k=k, radius=r, dtype=dtype))
