"""Unified query engine: plan (shape classes) -> stacked traversal ->
single on-device merge.

This is the one read path. Every caller — the static-tree convenience
(`core/search_jax.search`), the streaming snapshot search
(`index/search`), and the mutable datastore (`serve/retrieval`) — is a
thin adapter over `execute`/`search_tree`, so there is exactly one
implementation of dispatch, gid mapping, and the top-k merge.

Planner: a snapshot's segments are grouped by their power-of-two
*shape class* (`query/shapes.py`); all S segments of one class are
answered by a single stacked jit dispatch over a (S_pow2, …)-stacked
DeviceTree batch (padded with an all-dead dummy member). The default
dispatch is the fused two-phase traversal
(`constrained_knn_stacked_fused`): phase 1 collects each query's
pruned leaf frontier, phase 2 evaluates the gathered candidates with
the `leaf_topk_l2` Pallas kernel — bit-exact vs the classic in-loop
path, which remains as the `REPRO_FUSED_TRAVERSAL=0` escape hatch and
the fallback when a frontier overflows its cap. The delta arena joins
as a degenerate class via the fused
streaming top-k kernel (`kernels/topk_l2.py`) — its (Q, k) output is
already in `query/merge` sorted form, so it folds straight into the
snapshot merge. The per-part sorted k-bests are folded with
`query/merge.py` on device. So a mixed segments∪delta query costs
O(#classes) dispatches — O(1) per class, not O(#segments) — and the
jit cache is keyed on shape classes, not on every novel merge size.

The stacked batches are memoized (small LRU) on the segments' content
tokens: a steady read phase re-stacks nothing, and any seal / merge /
tombstone refreshes the affected tokens, invalidating exactly the
classes it touched.

Instrumentation lives on the process-wide observability registry
(`repro.obs`): dispatch and stack-cache counters are registry metrics
(atomic — the old module-global ints raced under threads), each
execute() stage runs in an `obs.span` (plan / stack / dispatch / delta /
merge — host timing + XLA profile annotation; `engine.dispatch` holds
one child span per step of the traversal, `engine.classic` for the
classic path and `core/search_jax` names the fused path's, and
`engine.merge` holds `engine.readback`), and an active
`obs.QueryTrace` additionally receives the per-query device-derived
paper metrics (nodes visited, leaves scanned, candidates evaluated).
`dispatch_count()` / `observed_signatures()` / `compile_stats()` /
`stack_stats()` remain as thin compat shims over the registry.
"""
from __future__ import annotations

import collections
import os
import threading
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from repro import obs
from repro.core import search_jax as sj
from repro.query import merge as qmerge
from repro.query import shapes
from repro.query.spec import QuerySpec


class EngineResult(NamedTuple):
    gids: np.ndarray            # (Q, k) global ids, -1 = no result
    distances: np.ndarray       # (Q, k) +inf where no result
    nodes_visited: Optional[np.ndarray]  # (Q,) traversal visits, or None
    # populated alongside nodes_visited (spec.return_visits or an active
    # QueryTrace): scanned non-empty leaves and distance-evaluated live
    # candidates per query — the paper's full accounting currency
    leaves_scanned: Optional[np.ndarray] = None
    points_examined: Optional[np.ndarray] = None


# -- instrumentation ---------------------------------------------------------
# All engine counters live on the obs registry (atomic increments; the
# registry's `snapshot()` exports them to BENCH_obs.json). Handles are
# cached here: registry reset() zeroes them in place, never orphans them.
# NOTE: disabling the registry (obs.REGISTRY.disable()) pauses these
# counters too — the compat shims below report whatever was recorded.
_C_TRAVERSAL = obs.REGISTRY.counter("engine.dispatches", kind="traversal")
_C_DELTA = obs.REGISTRY.counter("engine.dispatches", kind="delta")
_C_FUSED = obs.REGISTRY.counter("engine.fused_traversal", kind="used")
_C_FUSED_FB = obs.REGISTRY.counter(
    "engine.fused_traversal", kind="overflow_fallback"
)
_C_STACK_FULL = obs.REGISTRY.counter("engine.stack_cache", kind="full_build")
_C_STACK_INCR = obs.REGISTRY.counter("engine.stack_cache", kind="incremental")

# distinct stacked-dispatch signatures ever issued (returned by
# `observed_signatures()`, used by the compile-bound tests), guarded by
# its own lock — the old code mutated it with NO lock, so racing
# writers could lose elements mid-rehash
_SIGNATURES: set = set()
_SIG_LOCK = threading.Lock()


def dispatch_count() -> int:
    """ALL device search dispatches (traversal + delta). Compat shim
    over the registry counters."""
    return _C_TRAVERSAL.value + _C_DELTA.value


def observed_signatures() -> frozenset:
    with _SIG_LOCK:
        return frozenset(_SIGNATURES)


def compile_stats() -> dict:
    """Traversal jit-cache entry count + dispatch counters.

    `traversal_compiles` is None when the jit cache-size API is
    unavailable (it is private to jax) — callers must treat None as
    "unknown", never as zero."""
    # NOTE: `sj._gather_frontier` is deliberately NOT listed — its cache
    # keys on the data-dependent frontier width F_eff (a pow2 of the
    # observed max frontier), so e.g. a tombstone that shrinks the
    # frontier retraces it without constituting a new traversal program.
    sizes = [
        fn._cache_size()
        for fn in (
            sj.constrained_knn_stacked,
            sj._collect_frontier_stacked,
            sj._merge_segments,
            sj.constrained_knn,
            sj.knn,
        )
        if callable(getattr(fn, "_cache_size", None))
    ]
    return {
        "traversal_compiles": sum(sizes) if sizes else None,
        "traversal_dispatches": _C_TRAVERSAL.value,
        "dispatches": dispatch_count(),
    }


# -- planner -----------------------------------------------------------------
class ClassGroup(NamedTuple):
    cls: shapes.ShapeClass
    views: tuple  # SegmentViews of this class, token-sorted


def plan(snapshot) -> List[ClassGroup]:
    """Group a snapshot's live segments by shape class (token-sorted
    within a class so iteration — and any from-scratch stacked build —
    is deterministic; the stacked-batch cache keys on the token SET,
    since an incremental refresh may place a replacement segment in its
    predecessor's slot rather than in token order)."""
    groups = {}
    for view in snapshot.segments:
        if view.n_live == 0:  # fully tombstoned: nothing to dispatch
            continue
        cls = shapes.shape_class_of(
            view.dtree,
            view.stack_size,
            int(view.gids_dev.shape[0]),
            storage_dtype=getattr(view, "storage_dtype", "float32"),
        )
        groups.setdefault(cls, []).append(view)
    return [
        ClassGroup(cls, tuple(sorted(vs, key=lambda v: v.token)))
        for cls, vs in sorted(groups.items())
    ]


# -- stacked-batch cache -----------------------------------------------------
# LRU keyed on (class, gid-remap epoch, member-token set). The class
# carries the segments' STORAGE dtype (`ShapeClass.sdt`), so batches of
# different storage widths — whose leaf_q buffers could never
# concatenate — can never collide on one key. Per
# class at most TWO batches are retained — the current one plus the
# most recently used predecessor, which an MVCC reader holding an older
# snapshot may still be alternating with; older superseded batches are
# evicted eagerly so mutations cannot pin a pile of near-identical
# class-sized device copies. Guarded by a lock: snapshots promise
# torn-free concurrent readers, and those readers share this dict.
#
# Refresh is INCREMENTAL when membership barely changes: a tombstone
# replaces one segment's token, so instead of re-stacking the whole
# class batch (O(class) host restack + device upload) the predecessor
# batch is patched with an `.at[s].set` of just the changed member —
# O(segment) work. Slot assignment is therefore history-dependent (a
# replacement lands in its predecessor's slot); the merge over stacked
# slots is order-exact on distances, so results are unaffected.
_STACK_CACHE: "collections.OrderedDict" = collections.OrderedDict()
# sized for sharded serving: shards carry distinct cache tags (see
# Snapshot.cache_tag), so e.g. 4 shards x 2 classes each occupy 8
# buckets of current batches before any predecessor retention
_STACK_CACHE_MAX = 16
_STACK_LOCK = threading.Lock()


class _StackEntry(NamedTuple):
    stacked: sj.DeviceTree  # (S_pow2, …) batch, dummy-padded
    gids: jnp.ndarray       # (S_pow2, n) gid table
    slot_tokens: tuple      # token occupying each real (non-dummy) slot
    # quantized leaf storage of the batch, stacked alongside the trees
    # (None for f32 classes; qscale None unless the dtype carries
    # per-leaf scales)
    leaf_q: object = None   # (S_pow2, L, cap, d) storage dtype
    qscale: object = None   # (S_pow2, L) f32


def stack_stats() -> dict:
    """Counters for the stacked-batch cache: how many refreshes rebuilt
    a whole class batch vs patched a single member slot. Compat shim
    over the registry counters."""
    return {
        "full_builds": _C_STACK_FULL.value,
        "incremental_updates": _C_STACK_INCR.value,
    }


def _incremental_update(
    base: _StackEntry, group: ClassGroup
) -> Optional[_StackEntry]:
    """Patch `base` into the batch for `group` by replacing only the
    members whose token changed. Applicable when the member count is
    unchanged and at least one slot survives (else a full restack does
    the same work). Returns None when not applicable."""
    if len(base.slot_tokens) != len(group.views):
        return None
    old = set(base.slot_tokens)
    fresh = [v for v in group.views if v.token not in old]
    if not fresh or len(fresh) == len(group.views):
        return None  # identical (cache hit upstream) or all-new
    new_tokens = {v.token for v in group.views}
    free = [i for i, t in enumerate(base.slot_tokens) if t not in new_tokens]
    if len(free) != len(fresh):
        return None
    stacked, gids = base.stacked, base.gids
    leaf_q, qscale = base.leaf_q, base.qscale
    slot_tokens = list(base.slot_tokens)
    for s, view in zip(free, fresh):
        stacked = sj.DeviceTree(
            *[
                getattr(stacked, f).at[s].set(getattr(view.dtree, f))
                for f in sj.DeviceTree._fields
            ]
        )
        gids = gids.at[s].set(view.gids_dev)
        if leaf_q is not None:
            leaf_q = leaf_q.at[s].set(view.leaf_q)
            if qscale is not None:
                qscale = qscale.at[s].set(view.qscale)
        slot_tokens[s] = view.token
    return _StackEntry(stacked, gids, tuple(slot_tokens), leaf_q, qscale)


def _stacked_views(group: ClassGroup, epoch: int = 0, tag=None) -> _StackEntry:
    """The stacked batch entry for one shape class — (S_pow2, …) stacked
    DeviceTree, gid table, and (for quantized classes) the stacked
    narrow leaf buffers — memoized on (class incl. storage dtype,
    gid-remap epoch, member token set). The epoch is strictly a
    staleness fence: tokens already change on merges, but keying on the
    epoch too guarantees batches derived from a pre-remap gid layout
    can never be served to a post-remap reader. `tag` is the snapshot's
    cache_tag: indexes that legitimately coexist with the same shape
    class (serving shards) carry distinct tags, so class-level
    predecessor eviction never crosses index boundaries."""
    clskey = (group.cls, tag)
    key = (clskey, epoch, frozenset(v.token for v in group.views))
    with _STACK_LOCK:
        hit = _STACK_CACHE.get(key)
        if hit is not None:
            _STACK_CACHE.move_to_end(key)
            return hit
        # most recent predecessor batch of this class (same tag), if any
        base = next(
            (
                _STACK_CACHE[s]
                for s in reversed(_STACK_CACHE)
                if s[0] == clskey
            ),
            None,
        )
    # build outside the lock (two racing builders produce identical
    # content; last insert wins)
    entry = _incremental_update(base, group) if base is not None else None
    incremental = entry is not None
    if entry is None:
        dummy_dt, dummy_g = shapes.dummy_member(group.cls, jnp.float32)
        n_pad = shapes.next_pow2(len(group.views)) - len(group.views)
        # token-sorted slots so a from-scratch build is deterministic
        views = sorted(group.views, key=lambda v: v.token)
        trees = [v.dtree for v in views] + [dummy_dt] * n_pad
        leaf_q = qscale = None
        if group.cls.sdt != "float32":
            dq_lq, dq_sc = shapes.dummy_quantized(group.cls)
            leaf_q = jnp.stack(
                [v.leaf_q for v in views] + [dq_lq] * n_pad
            )
            if dq_sc is not None:
                qscale = jnp.stack(
                    [v.qscale for v in views] + [dq_sc] * n_pad
                )
        entry = _StackEntry(
            stacked=sj.DeviceTree(
                *[
                    jnp.stack([getattr(t, f) for t in trees])
                    for f in sj.DeviceTree._fields
                ]
            ),
            gids=jnp.stack(
                [v.gids_dev for v in views] + [dummy_g] * n_pad
            ),
            slot_tokens=tuple(v.token for v in views),
            leaf_q=leaf_q,
            qscale=qscale,
        )
    # registry counters are atomic on their own (stack_stats feeds
    # exact-count test assertions; racing cache-missers each count)
    (_C_STACK_INCR if incremental else _C_STACK_FULL).inc()
    with _STACK_LOCK:
        same = [s for s in _STACK_CACHE if s[0] == clskey]
        for stale in same[:-1]:  # keep only the most recent predecessor
            del _STACK_CACHE[stale]
        _STACK_CACHE[key] = entry
        while len(_STACK_CACHE) > _STACK_CACHE_MAX:
            _STACK_CACHE.popitem(last=False)
    return entry


def _fused_enabled() -> bool:
    """Two-phase kernel-leaf traversal is the default read path;
    `REPRO_FUSED_TRAVERSAL=0` is the bisection escape hatch back to the
    classic in-loop jnp leaf evaluation."""
    return os.environ.get("REPRO_FUSED_TRAVERSAL", "1") != "0"


def _dispatch_stacked(
    stacked,
    gids,
    q,
    rb,
    k: int,
    stack_size: int,
    cls,
    leaf_q=None,
    qscale=None,
    qerr: float = 0.0,
):
    _C_TRAVERSAL.inc()
    with _SIG_LOCK:
        _SIGNATURES.add(
            (cls, int(gids.shape[0]), int(q.shape[0]), k, str(q.dtype))
        )
    with obs.span("engine.dispatch"):
        # Fused two-phase traversal (collect leaf frontier, evaluate the
        # gathered candidates with the leaf_topk_l2 kernel) is bit-exact
        # vs the classic path and is the default. The COMPUTE dtype is
        # f32; any other traversal dtype (search_tree overrides) takes
        # the classic path. Quantized classes hand their narrow leaf
        # buffers to the fused path, which streams them and rescores
        # survivors from the stacked f32 leaves — results stay
        # bit-identical (certified per dispatch; certificate failure
        # re-runs that dispatch in f32, counted, never truncated). A
        # frontier-cap overflow returns None — fall back to the classic
        # in-loop f32 path and count it.
        if _fused_enabled() and q.dtype == jnp.float32:
            res = sj.constrained_knn_stacked_fused(
                stacked,
                gids,
                q,
                rb,
                k,
                stack_size,
                leaf_q=leaf_q,
                qscale=qscale,
                qerr=qerr,
            )
            if res is not None:
                _C_FUSED.inc()
                return res
            _C_FUSED_FB.inc()
        with obs.span("engine.classic"):
            return sj.constrained_knn_stacked(
                stacked, gids, q, rb, k, stack_size
            )


# -- executor ----------------------------------------------------------------
def execute(snapshot, queries, spec: QuerySpec) -> EngineResult:
    """Exact constrained-KNN over a streaming snapshot (segments∪delta)."""
    k = spec.k
    # the streaming COMPUTE path is f32 end-to-end (queries, pruning
    # arithmetic, the delta kernel, rescoring): reject other compute
    # dtypes instead of silently promoting/demoting depending on batch
    # padding. dtype overrides are for static trees (search_tree),
    # which are devicized per request. Segment STORAGE width is
    # independent: each segment carries its own storage dtype
    # (bf16/int8 leaf buffers), grouped into storage-aware shape
    # classes and rescored back to exact f32 results.
    if jnp.dtype(spec.dtype) != jnp.dtype(jnp.float32):
        raise ValueError(
            "snapshot search compute is float32-only; QuerySpec.dtype "
            "overrides apply to search_tree (segment storage dtype is "
            f"per-segment, got compute {jnp.dtype(spec.dtype).name})"
        )
    qt = obs.trace.current_query_trace()
    # an active QueryTrace wants the paper metrics even when the caller
    # did not ask for them on the result
    want_stats = spec.return_visits or qt is not None
    q_host = np.asarray(queries).reshape(-1, snapshot.dim)
    nq = q_host.shape[0]
    if qt is not None:
        qt.set_metric("n_live", snapshot.n_live)
        qt.set_metric("n_segments", len(snapshot.segments))
    if snapshot.n_live == 0:
        # all points tombstoned (or never inserted): answer on the host,
        # zero device dispatches
        zeros = np.zeros(nq, np.int32)
        if qt is not None:
            qt.set_metric("n_classes", 0)
            qt.set_metric("delta_candidates", 0)
            qt.set_metric("nodes_visited", zeros)
            qt.set_metric("leaves_scanned", zeros)
            qt.set_metric("candidates_evaluated", zeros)
        return EngineResult(
            gids=np.full((nq, k), -1, np.int32),
            distances=np.full((nq, k), np.inf, np.float32),
            nodes_visited=zeros if spec.return_visits else None,
            leaves_scanned=zeros if spec.return_visits else None,
            points_examined=zeros if spec.return_visits else None,
        )
    dtype = jnp.dtype(spec.dtype)
    q = jnp.asarray(q_host, dtype)
    rb = jnp.broadcast_to(jnp.asarray(spec.radius, dtype), (nq,))

    parts: List[Tuple[jnp.ndarray, jnp.ndarray]] = []
    visits = leaves = cands = None
    with obs.span("engine.plan"):
        groups = plan(snapshot)
    for group in groups:
        with obs.span("engine.stack"):
            entry = _stacked_views(
                group,
                getattr(snapshot, "epoch", 0),
                getattr(snapshot, "cache_tag", None),
            )
        res = _dispatch_stacked(
            entry.stacked,
            entry.gids,
            q,
            rb,
            k,
            group.cls.stack_size,
            group.cls,
            leaf_q=entry.leaf_q,
            qscale=entry.qscale,
            # one containment certificate covers the whole stacked
            # dispatch, so it must assume the worst member's bound
            qerr=max((v.qerr for v in group.views), default=0.0),
        )
        parts.append((res.distances, res.gids))
        if want_stats:
            # each pow2-padding dummy contributes exactly one root visit
            # per query; subtract it so accounting matches the real
            # trees. Leaves/candidates need no correction: the dummy's
            # only leaf is empty, so it scans nothing
            n_pad = shapes.next_pow2(len(group.views)) - len(group.views)
            gv = res.nodes_visited - n_pad
            visits = gv if visits is None else visits + gv
            lv, pe = res.leaves_visited, res.points_examined
            leaves = lv if leaves is None else leaves + lv
            cands = pe if cands is None else cands + pe
    delta_cands = 0
    if snapshot.delta_n_live > 0:
        from repro.index import delta as delta_mod

        _C_DELTA.inc()
        # degenerate-class dispatch: the fused kernel streams the arena
        # once, selects in-kernel, and returns (Q, k) already in the
        # sorted-merge convention — no reshaping before the fold
        with obs.span("engine.delta"):
            dd, dg = delta_mod.search(
                snapshot.delta_points, snapshot.delta_gids, q, k, rb
            )
        parts.append((dd, dg))
        # the arena scan evaluates every live slot's distance per query
        delta_cands = int(snapshot.delta_n_live)

    with obs.span("engine.merge"):
        d, g = qmerge.merge_parts(parts, k)
        # materialize on the host so both execute() paths (and therefore
        # Datastore.search) honor the declared np.ndarray contract
        with obs.span("engine.readback"):
            g_host = np.asarray(g, np.int32)
            d_host = np.asarray(d, np.float32)
    if want_stats:
        visits = (
            np.asarray(visits, np.int32)
            if visits is not None
            else np.zeros(nq, np.int32)
        )
        leaves = (
            np.asarray(leaves, np.int32)
            if leaves is not None
            else np.zeros(nq, np.int32)
        )
        cands = (
            np.asarray(cands, np.int64)
            if cands is not None
            else np.zeros(nq, np.int64)
        ) + delta_cands
        if qt is not None:
            qt.set_metric("n_classes", len(groups))
            qt.set_metric("delta_candidates", delta_cands)
            qt.set_metric("nodes_visited", visits)
            qt.set_metric("leaves_scanned", leaves)
            qt.set_metric("candidates_evaluated", cands)
    return EngineResult(
        gids=g_host,
        distances=d_host,
        nodes_visited=visits if spec.return_visits else None,
        leaves_scanned=leaves if spec.return_visits else None,
        points_examined=cands if spec.return_visits else None,
    )


def search_tree(tree, queries, spec: QuerySpec) -> sj.KnnResult:
    """Static host tree through the same engine: padded to its shape
    class and dispatched as an S=1 stacked batch, so a static tree and
    a streaming segment of the same class share one compiled program."""
    dtype = jnp.dtype(spec.dtype)
    dt = shapes.pad_device_tree(sj.device_tree(tree, dtype))
    stack_size = shapes.padded_stack_size(sj.max_depth(tree))
    gids = shapes.pad_gids(jnp.arange(tree.n_points, dtype=jnp.int32))
    cls = shapes.shape_class_of(dt, stack_size, int(gids.shape[0]))
    q = jnp.asarray(np.asarray(queries).reshape(-1, cls.dim), dtype)
    rb = jnp.broadcast_to(jnp.asarray(spec.radius, dtype), q.shape[:1])
    stacked = sj.DeviceTree(*[x[None] for x in dt])
    res = _dispatch_stacked(stacked, gids[None], q, rb, spec.k, stack_size, cls)
    return sj.KnnResult(
        indices=res.gids,
        distances=res.distances,
        nodes_visited=res.nodes_visited,
        leaves_visited=res.leaves_visited,
        points_examined=res.points_examined,
    )
