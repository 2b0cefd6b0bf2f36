"""Pallas TPU kernel: fused streaming constrained top-k over L2 distances.

Every exhaustive scan in the system — the delta arena, the brute
referent, and the query engine's degenerate class — needs only the k
nearest live points within a radius, yet the unfused path materializes
the full (Q, N) distance matrix in HBM and argsorts every row. This
kernel fuses the selection into the distance scan: the (bm, bn) MXU
distance blocks are computed exactly like ``pairwise_l2.py``
(``q² + p² - 2qp`` accumulated over the K grid dimension), but instead
of writing each block back, a per-query running sorted top-k stays
resident in VMEM across the N grid dimension and each block is folded
into it on the spot. HBM traffic drops from O(Q·N) distance writes plus
an O(N log N) row sort to a single streaming read of ``p`` and an
O(Q·k) result write.

In-kernel selection (all VPU-friendly compare-exchange networks, no
sort primitive):

  1. *bitonic partial selection* — the bn block distances are reduced
     to their kp = pow2(k) smallest: sort each kp-chunk (the first
     stages of a bitonic sort), then a tournament of chunk-pair
     compare-exchanges (elementwise min of an ascending/descending pair
     is a bitonic sequence holding the pair's kp smallest) followed by
     a log(kp) bitonic re-sort of the winner, halving the live chunks
     each round;
  2. *carried merge* — the carried k-best (ascending) concatenated
     with the block's k-best (descending) is bitonic, so one log(2kp)
     bitonic merge yields the new carried k-best.

The radius gate and gid-liveness mask are applied to each block before
selection (masked lanes read +inf), so dead arena slots and
out-of-range points never leave the kernel. Ordering matches the
``query/merge`` sorted-merge convention bit-for-bit: candidates are
keyed lexicographically by (distance, slot index), which is exactly
the order a stable argsort of the masked distance row would produce —
ties go to the lower slot.

Squared-distance selection (two-pass radius refinement): the kernel
never takes a square root — blocks are compared, gated, and selected
as SQUARED distances, with the radius squared *conservatively upward*
(`radius_sq_upper`) so no point with euclidean distance <= r can be
rejected in-kernel. The wrapper then takes `sqrt` of only the k
survivors and applies the exact euclidean gate `sqrt(sq) <= r`. This
is exact, not approximate: any conservative false admit has a strictly
larger squared distance than every true candidate (sqrt is monotone),
so false admits can only occupy trailing slots of the k-window — the
final mask removes them without ever having displaced a true result.
The full-width per-element sqrt this replaces sat on the VPU critical
path of every (bm, bn) block.

``leaf_topk_l2`` is the batched-candidates variant used by the fused
tree traversal: each query row carries its OWN (C, D) candidate matrix
(the gathered leaf frontier of that query, in DFS visit order). Its
distance block uses the difference form ``Σ (q - c)²`` — the same f32
rounding as the traversal's in-loop leaf evaluation, which the
two-phase path must match bit-for-bit — and the slot tie-break key
reproduces the traversal's insertion order exactly.

All comparator stages address XOR partners with two static lane
rotations (`pltpu.roll`) and a lane-iota select, and take sort
directions from int32 lane arithmetic — the forms Mosaic lowers. No
gathers, scatters, dynamic indexing, or lane-axis reshapes (Mosaic
refuses to split the lane axis into (pairs, 2, stride)).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_I32_MAX = np.iinfo(np.int32).max

# conservative relative slack for the in-kernel squared radius gate:
# with correctly-rounded f32 ops, sqrt(sq) <= r implies
# sq < (r*r) * (1 + 1.76 * 2^-23); 2^-20 leaves an 8x margin
_R2_SLACK = 1.0 + 2.0**-20


def radius_sq_upper(r):
    """Conservatively-rounded squared radius: every candidate whose
    euclidean f32 distance satisfies `sqrt(sq) <= r` also satisfies
    `sq <= radius_sq_upper(r)` — the sound in-kernel squared gate of
    the two-pass radius refinement (exactness restored by the final
    `sqrt(sq) <= r` mask on the k survivors)."""
    r = jnp.asarray(r)
    return r * r * jnp.asarray(_R2_SLACK, r.dtype)


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def selection_stages(kp: int, bn: int) -> int:
    """Compare-exchange stages per (bm, bn) block: chunk sort +
    tournament rounds + the carried 2kp merge. Used by `block_plan`
    and the roofline benchmarks to cost the VPU selection network."""
    lk, lb = int(np.log2(kp)), int(np.log2(bn))
    chunk_sort = lk * (lk + 1) // 2
    tournament = (lb - lk) * (1 + lk)
    carried = lk + 1
    return chunk_sort + tournament + carried


def block_plan(
    m: int,
    n: int,
    d: int,
    k: int,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    itemsize: int = 4,
) -> dict:
    """Resolved launch geometry + analytic cost of one fused top-k call.

    Mirrors the clamp logic of `topk_l2` exactly — the single source of
    truth shared by the wrapper accounting (`ops.py`), the roofline
    benchmarks (`benchmarks/kernels_bench.py`), and the block autotuner
    (`kernels/autotune.py`).

    `flops` / `hbm_bytes` are the block-independent *algorithmic*
    counts (what the workload irreducibly costs); `padded_flops` /
    `stream_bytes` / `vmem_bytes` are the block-DEPENDENT terms the
    autotuner ranks on: padding waste, pipeline refetch traffic (the q
    tile is re-read once per N block, the p tile once per M block),
    and the VMEM working set.

    `itemsize` is the STORAGE width (bytes/elem) of the streamed point
    buffer — 4 for f32, 2 for bf16, 1 for int8 — so byte accounting
    reflects what actually crosses HBM, not a hardcoded f32 width.
    Queries, gids, and outputs stay f32/i32.
    """
    kp = _next_pow2(k)
    bm = min(bm, _round_up(m, 8))
    bn = max(kp, min(_next_pow2(bn), _round_up(_next_pow2(n), 128)))
    bk = min(bk, _round_up(d, 128))
    mp, np_, dp = _round_up(m, bm), _round_up(n, bn), _round_up(d, bk)
    grid = (mp // bm, np_ // bn, dp // bk)
    stages = selection_stages(kp, bn)
    return {
        "kp": kp,
        "bm": bm,
        "bn": bn,
        "bk": bk,
        "grid": grid,
        "blocks": grid[0] * grid[1] * grid[2],
        # shared MXU matmul + ~8 elementary VPU ops per lane per
        # compare-exchange stage of the selection network
        "flops": 2 * m * n * d
        + 2 * (m + n) * d
        + 8 * m * n * stages,
        # stream q, p, gids once; write the (Q, kp) d/gid/slot triple
        "hbm_bytes": m * d * 4 + n * d * itemsize + n * 4 + m * kp * 12,
        # block-aware autotuner terms ------------------------------------
        "padded_flops": 2 * mp * np_ * dp
        + 2 * (mp + np_) * dp
        + 8 * mp * np_ * stages,
        "stream_bytes": mp * dp * 4 * grid[1]   # q refetched per N block
        + (np_ * dp * itemsize + np_ * 4) * grid[0]  # p+gids per M block
        + mp * kp * 12,
        "vmem_bytes": (bm * bk + bm * bn + 3 * bm * kp + bm + bn) * 4
        + bn * bk * itemsize,
    }


def _cmpx(d, g, s, stride: int, size: int = 0, desc: bool = False):
    """One compare-exchange stage at XOR distance `stride` along the
    lane axis: lane i pairs with lane i ^ stride. The lower lane of a
    pair (bit `stride` clear) reads its partner `stride` lanes up, the
    upper lane reads it `stride` lanes down — two lane rotations and a
    lane-iota select, which is what Mosaic lowers natively (no
    sub-vreg reshapes, gathers or dynamic indexing).

    Each pair ends up (min, max) by the lexicographic (distance, slot)
    key in an ascending group, (max, min) in a descending one. With
    `size` set, lane i's group is ascending iff (i & size) == 0 — a
    bitonic sort stage — and `desc` flips every group; with size=0 the
    whole row shares the direction `desc` picks (merge stages).
    Directions are int32 lane arithmetic, never bool compares. Equal
    keys never swap, so both lanes of a pair always agree."""
    bm_, width = d.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm_, width), 1)
    is_lo = (lane & stride) == 0
    # jnp.roll semantics: roll(x, w - t)[i] = x[i + t], roll(x, t)[i] = x[i - t]
    partner = lambda x: jnp.where(
        is_lo,
        pltpu.roll(x, width - stride, 1),
        pltpu.roll(x, stride, 1),
    )
    pd, pg, ps = partner(d), partner(g), partner(s)
    partner_first = (pd < d) | ((pd == d) & (ps < s))
    self_first = (d < pd) | ((d == pd) & (s < ps))
    # 0 where this lane keeps the pair's min, 1 where it keeps the max
    flip = (lane // stride) & 1
    if size:
        flip = flip ^ ((lane // size) & 1)
    keep_min = flip == int(desc)
    take = (keep_min & partner_first) | (~keep_min & self_first)
    return (
        jnp.where(take, pd, d),
        jnp.where(take, pg, g),
        jnp.where(take, ps, s),
    )


def _block_topk_desc(d, g, s, kp: int, bn: int):
    """kp smallest of each row of a (bm, bn) block, sorted DESCENDING
    into lanes [0, kp) — descending so the caller can append it to an
    ascending carried list and get a bitonic sequence for free."""
    full_desc = kp == bn  # degenerate: the whole block IS the selection
    # stage A: sort each kp-chunk, directions alternating by chunk (a
    # full descending sort when kp == bn)
    size = 2
    while size <= kp:
        stride = size // 2
        while stride >= 1:
            d, g, s = _cmpx(d, g, s, stride, size, desc=full_desc)
            stride //= 2
        size *= 2
    # stage B: tournament — compare-exchange chunk pairs (elementwise
    # min of an asc/desc sorted pair is bitonic and holds the pair's kp
    # smallest), then re-sort the winner chunk for the next round;
    # loser chunks only ever pair with other losers
    span = kp
    while span < bn:
        d, g, s = _cmpx(d, g, s, span)
        nxt = 2 * span
        stride = kp // 2
        while stride >= 1:
            # alternate winner directions for the next round; the last
            # surviving chunk is sorted descending for the caller
            if nxt < bn:
                d, g, s = _cmpx(d, g, s, stride, nxt)
            else:
                d, g, s = _cmpx(d, g, s, stride, desc=True)
            stride //= 2
        span = nxt
    return d, g, s


# rows per pass of the selection network: one f32 sublane tile. The
# network is unrolled per pass, so looping over row tiles keeps the
# Mosaic program (and its compile time) independent of bm
_SEL_ROWS = 8


def _select_block(
    acc_ref, g_ref, r_ref, od_ref, og_ref, os_ref, j, *, kp: int, bm: int,
    bn: int
):
    """Fold the finished (bm, bn) squared-distance block into the
    carried ascending k-best, `_SEL_ROWS` rows per loop step. `g_ref`
    holds one gid row shared by every query (1, bn) or one per query
    (bm, bn). No sqrt in-kernel: `r_ref` carries the conservatively
    squared radius (`radius_sq_upper`) and the wrapper refines the k
    survivors with the exact euclidean gate."""
    rows_n = min(_SEL_ROWS, bm)
    shared_gids = g_ref.shape[0] != bm

    def body(t, carry):
        rows = pl.ds(pl.multiple_of(t * rows_n, rows_n), rows_n)
        d = jnp.maximum(acc_ref[rows, :], 0.0)
        g = jnp.broadcast_to(
            g_ref[...] if shared_gids else g_ref[rows, :], (rows_n, bn)
        )
        idx = jax.lax.broadcasted_iota(jnp.int32, (rows_n, bn), 1)
        slot = j * bn + idx  # global slot / visit order: the tie-break
        ok = (g >= 0) & (d <= r_ref[rows, :])  # liveness & radius gates
        d = jnp.where(ok, d, jnp.inf)
        s = jnp.where(ok, slot, _I32_MAX)

        d, g, s = _block_topk_desc(d, g, s, kp, bn)

        # carried (ascending) ++ block k-best (descending) is bitonic:
        # one merge network re-establishes the ascending carried k-best
        md = jnp.concatenate([od_ref[rows, :], d[:, :kp]], axis=1)
        mg = jnp.concatenate([og_ref[rows, :], g[:, :kp]], axis=1)
        ms = jnp.concatenate([os_ref[rows, :], s[:, :kp]], axis=1)
        stride = kp
        while stride >= 1:
            md, mg, ms = _cmpx(md, mg, ms, stride)
            stride //= 2
        od_ref[rows, :] = md[:, :kp]
        og_ref[rows, :] = mg[:, :kp]
        os_ref[rows, :] = ms[:, :kp]
        return carry

    jax.lax.fori_loop(0, bm // rows_n, body, 0)


def _kernel(
    q_ref, p_ref, g_ref, r_ref, od_ref, og_ref, os_ref, acc_ref,
    *, k_steps: int, kp: int, bm: int, bn: int
):
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when((j == 0) & (kk == 0))
    def _init_best():
        od_ref[...] = jnp.full_like(od_ref, jnp.inf)
        og_ref[...] = jnp.full_like(og_ref, -1)
        os_ref[...] = jnp.full_like(os_ref, _I32_MAX)

    @pl.when(kk == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- distance block: identical accumulation to pairwise_l2 ----------
    q = q_ref[...].astype(jnp.float32)  # (bm, bk)
    p = p_ref[...].astype(jnp.float32)  # (bn, bk)
    qp = jax.lax.dot_general(
        q, p, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bm, bn) on the MXU
    qn = (q * q).sum(axis=1, keepdims=True)
    pn = (p * p).sum(axis=1, keepdims=True).T
    acc_ref[...] += qn + pn - 2.0 * qp

    # ---- selection: only on the last K step, once per (i, j) block ------
    @pl.when(kk == k_steps - 1)
    def _select():
        _select_block(
            acc_ref, g_ref, r_ref, od_ref, og_ref, os_ref, j,
            kp=kp, bm=bm, bn=bn,
        )


@functools.partial(
    jax.jit, static_argnames=("k", "bm", "bn", "bk", "interpret")
)
def topk_l2(
    q: jax.Array,      # (Q, D) queries
    p: jax.Array,      # (N, D) points (streamed once)
    gids: jax.Array,   # (N,) i32 ids; negative = dead/empty slot
    r,                 # scalar or (Q,) euclidean radius gate (inf = none)
    k: int,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
):
    """Constrained k-nearest via one streaming fused scan of ``p``.

    Returns ``(distances (Q, k) f32, ids (Q, k) i32)`` ascending-sorted
    per row with (+inf, -1) where fewer than k live points fall within
    radius r — ordering identical to a stable argsort of the masked
    distance row (the `query/merge` convention). Arbitrary Q, N, D;
    inputs are zero-padded to block multiples and padded point slots
    carry gid -1, so padding can never be selected.
    """
    m, d = q.shape
    n, d2 = p.shape
    assert d == d2, (q.shape, p.shape)
    assert gids.shape == (n,), (gids.shape, n)
    if m == 0 or n == 0:  # empty scan: the all-padding answer, no grid
        return (
            jnp.full((m, k), jnp.inf, jnp.float32),
            jnp.full((m, k), -1, jnp.int32),
        )
    kp = _next_pow2(k)
    bm = min(bm, _round_up(m, 8))
    # the lane-axis selection network needs bn pow2 and >= the carried
    # width; 128 keeps full lanes on TPU
    bn = max(kp, min(_next_pow2(bn), _round_up(_next_pow2(n), 128)))
    bk = min(bk, _round_up(d, 128))
    mp, np_, dp = _round_up(m, bm), _round_up(n, bn), _round_up(d, bk)
    qpad = jnp.zeros((mp, dp), jnp.float32).at[:m, :d].set(
        jnp.asarray(q, jnp.float32)
    )
    ppad = jnp.zeros((np_, dp), jnp.float32).at[:n, :d].set(
        jnp.asarray(p, jnp.float32)
    )
    gpad = jnp.full((1, np_), -1, jnp.int32).at[0, :n].set(
        jnp.asarray(gids, jnp.int32)
    )
    rb = jnp.broadcast_to(jnp.asarray(r, jnp.float32), (m,))
    # the kernel selects SQUARED distances gated by the conservatively-
    # squared radius; exactness is restored on the k survivors below
    rpad = jnp.zeros((mp, 1), jnp.float32).at[:m, 0].set(
        radius_sq_upper(rb)
    )
    k_steps = dp // bk
    grid = (mp // bm, np_ // bn, k_steps)
    with jax.named_scope("kernel.topk_l2"):
        out_d, out_g, _slots = pl.pallas_call(
            functools.partial(
                _kernel, k_steps=k_steps, kp=kp, bm=bm, bn=bn
            ),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
                pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
                pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bm, kp), lambda i, j, kk: (i, 0)),
                pl.BlockSpec((bm, kp), lambda i, j, kk: (i, 0)),
                pl.BlockSpec((bm, kp), lambda i, j, kk: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((mp, kp), jnp.float32),
                jax.ShapeDtypeStruct((mp, kp), jnp.int32),
                jax.ShapeDtypeStruct((mp, kp), jnp.int32),
            ],
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
        )(qpad, ppad, gpad, rpad)
    # two-pass radius refinement: sqrt only the k survivors, then apply
    # the exact euclidean gate — conservative false admits have strictly
    # larger squared distance than every true candidate, so they sit in
    # trailing slots and masking them cannot reorder true results
    sq = out_d[:m, :k]
    dl = jnp.sqrt(sq)
    ok = dl <= rb[:, None]
    dd = jnp.where(ok, dl, jnp.inf)
    gg = jnp.where(ok, out_g[:m, :k], -1)
    return dd, gg


def leaf_block_plan(
    r: int,
    c: int,
    d: int,
    k: int,
    *,
    bm: int = 8,
    bn: int = 128,
    bk: int = 512,
    itemsize: int = 4,
) -> dict:
    """Launch geometry + analytic cost of one batched leaf-candidate
    call (`leaf_topk_l2`): each of the `r` rows scans its OWN (c, d)
    candidate matrix, so the distance block is a batched matvec and the
    candidate tensor itself dominates the stream. Mirrors the wrapper's
    clamp logic exactly, like `block_plan` does for `topk_l2`.

    `itemsize` is the candidate STORAGE width (4 = f32, 2 = bf16,
    1 = int8). int8 candidates also stream a per-candidate f32 scale
    row (the broadcast per-leaf scale), accounted below; queries, gids,
    and the output triple stay f32/i32 regardless.
    """
    kp = _next_pow2(k)
    bm = min(bm, _round_up(r, 8))
    bn = max(kp, min(_next_pow2(bn), _round_up(_next_pow2(c), 128)))
    bk = min(bk, _round_up(d, 128))
    rp, cp, dp = _round_up(r, bm), _round_up(c, bn), _round_up(d, bk)
    grid = (rp // bm, cp // bn, dp // bk)
    stages = selection_stages(kp, bn)
    # int8 is the only storage dtype that carries a dequant scale input
    scale_bytes = rp * cp * 4 if itemsize == 1 else 0
    return {
        "kp": kp,
        "bm": bm,
        "bn": bn,
        "bk": bk,
        "grid": grid,
        "blocks": grid[0] * grid[1] * grid[2],
        # difference-form distances (sub, mul, add) + selection network
        "flops": 3 * r * c * d + 8 * r * c * stages,
        # q + per-row candidates + gids streamed once, (r, kp) triple out
        "hbm_bytes": r * d * 4
        + r * c * d * itemsize
        + r * c * 4
        + (r * c * 4 if itemsize == 1 else 0)
        + r * kp * 12,
        "padded_flops": 3 * rp * cp * dp + 8 * rp * cp * stages,
        # candidates/gids are private per row — fetched exactly once;
        # only the q tile is re-read per C block
        "stream_bytes": rp * dp * 4 * grid[1]
        + (rp * cp * dp * itemsize + rp * cp * 4 + scale_bytes)
        + rp * kp * 12,
        "vmem_bytes": (bm * bk + 2 * bm * bn + 3 * bm * kp + bm) * 4
        + bm * bn * bk * itemsize
        + (bm * bn * 4 if itemsize == 1 else 0),
    }


def _leaf_kernel(
    *refs, k_steps: int, kp: int, bm: int, bn: int, has_scale: bool
):
    if has_scale:
        (q_ref, c_ref, sc_ref, g_ref, r_ref,
         od_ref, og_ref, os_ref, acc_ref) = refs
    else:
        q_ref, c_ref, g_ref, r_ref, od_ref, og_ref, os_ref, acc_ref = refs
        sc_ref = None
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when((j == 0) & (kk == 0))
    def _init_best():
        od_ref[...] = jnp.full_like(od_ref, jnp.inf)
        og_ref[...] = jnp.full_like(og_ref, -1)
        os_ref[...] = jnp.full_like(os_ref, _I32_MAX)

    @pl.when(kk == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- batched distance block: each row vs its own candidates ---------
    # deliberately the DIFFERENCE form, not the matmul decomposition:
    # the traversal fallback evaluates leaves as ((pts - q)**2).sum(-1),
    # and the two-phase path promises bit-identical results to it, so
    # the kernel must round exactly the same way. Leaf frontiers are
    # small (F·cap candidates per row) and the scan is memory-bound on
    # the gathered candidate tensor, so the lost MXU matmul is not the
    # bottleneck here the way it is in the shared-points kernels.
    #
    # Candidates may arrive quantized (bf16, or int8 + per-candidate f32
    # scale): they are widened to f32 right out of VMEM, so HBM streams
    # the narrow buffer while the distance math stays f32. The over-
    # fetch + exact-rescore pass downstream restores bit-exactness.
    q = q_ref[...].astype(jnp.float32)  # (bm, bk)
    c = c_ref[...].astype(jnp.float32)  # (bm, bn, bk)
    if sc_ref is not None:
        # the dequant product must round identically to the two-step
        # oracle (`quantize.dequantize`: widen, one f32 multiply) even
        # when the backend contracts it into the subtraction below as a
        # single-rounding fma (XLA:CPU does; an HLO optimization
        # barrier does not stop LLVM codegen contraction). The encoder
        # guarantees this structurally: int8 scales are powers of two
        # (`quantize.quantize_leaves`), so `c * sc` is a pure exponent
        # shift — EXACT in f32 — and fused vs two-step rounding
        # coincide bitwise on every backend. That exactness is what
        # lets the containment certificate treat the kernel's k'-th
        # key as a bitwise fact of the dequantized candidate set.
        c = c * sc_ref[...][:, :, None]
    diff = q[:, None, :] - c
    acc_ref[...] += (diff * diff).sum(axis=2)

    # ---- selection: squared domain, identical network to `_kernel` ------
    @pl.when(kk == k_steps - 1)
    def _select():
        _select_block(
            acc_ref, g_ref, r_ref, od_ref, og_ref, os_ref, j,
            kp=kp, bm=bm, bn=bn,
        )


def _leaf_call(
    q, cands, cscale, cgids, r_sq, k, bm, bn, bk, interpret
):
    """Shared pallas_call body of the leaf-candidate kernels: pads to
    block multiples (candidates in their STORAGE dtype — f32, bf16, or
    int8 with a per-candidate f32 `cscale`), launches `_leaf_kernel`,
    and returns the raw per-row ``(squared (R, k), gids (R, k),
    slots (R, k))`` triple selected by the lexicographic
    (squared distance, slot) key. `r_sq` is the ALREADY-squared
    conservative in-kernel gate — callers widen it themselves
    (`radius_sq_upper`, plus the quantization error bound on the
    quantized path)."""
    m, d = q.shape
    m2, c, d2 = cands.shape
    assert (m, d) == (m2, d2), (q.shape, cands.shape)
    assert cgids.shape == (m, c), (cgids.shape, (m, c))
    kp = _next_pow2(k)
    bm = min(bm, _round_up(m, 8))
    bn = max(kp, min(_next_pow2(bn), _round_up(_next_pow2(c), 128)))
    bk = min(bk, _round_up(d, 128))
    mp, cp, dp = _round_up(m, bm), _round_up(c, bn), _round_up(d, bk)
    qpad = jnp.zeros((mp, dp), jnp.float32).at[:m, :d].set(
        jnp.asarray(q, jnp.float32)
    )
    cpad = jnp.zeros((mp, cp, dp), cands.dtype).at[:m, :c, :d].set(cands)
    gpad = jnp.full((mp, cp), -1, jnp.int32).at[:m, :c].set(
        jnp.asarray(cgids, jnp.int32)
    )
    rpad = jnp.zeros((mp, 1), jnp.float32).at[:m, 0].set(
        jnp.asarray(r_sq, jnp.float32)
    )
    k_steps = dp // bk
    grid = (mp // bm, cp // bn, k_steps)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bm, bn, bk), lambda i, j, kk: (i, j, kk)),
    ]
    operands = [qpad, cpad]
    if cscale is not None:
        assert cscale.shape == (m, c), (cscale.shape, (m, c))
        scpad = jnp.zeros((mp, cp), jnp.float32).at[:m, :c].set(
            jnp.asarray(cscale, jnp.float32)
        )
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        operands.append(scpad)
    in_specs += [
        pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
    ]
    operands += [gpad, rpad]
    with jax.named_scope("kernel.leaf_topk_l2"):
        out_d, out_g, out_s = pl.pallas_call(
            functools.partial(
                _leaf_kernel,
                k_steps=k_steps,
                kp=kp,
                bm=bm,
                bn=bn,
                has_scale=cscale is not None,
            ),
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((bm, kp), lambda i, j, kk: (i, 0)),
                pl.BlockSpec((bm, kp), lambda i, j, kk: (i, 0)),
                pl.BlockSpec((bm, kp), lambda i, j, kk: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((mp, kp), jnp.float32),
                jax.ShapeDtypeStruct((mp, kp), jnp.int32),
                jax.ShapeDtypeStruct((mp, kp), jnp.int32),
            ],
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
        )(*operands)
    return out_d[:m, :k], out_g[:m, :k], out_s[:m, :k]


@functools.partial(
    jax.jit, static_argnames=("k", "bm", "bn", "bk", "interpret")
)
def leaf_topk_l2(
    q: jax.Array,       # (R, D) one row per (segment, query) pair
    cands: jax.Array,   # (R, C, D) per-row gathered leaf candidates
    cgids: jax.Array,   # (R, C) i32 ids; negative = hole / dead slot
    r,                  # scalar or (R,) euclidean radius gate
    k: int,
    *,
    bm: int = 8,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
):
    """Constrained k-nearest where every query row carries its own
    candidate matrix — the phase-2 evaluator of the fused two-phase
    traversal (each row's candidates are its gathered leaf frontier in
    DFS visit order, so the (distance, slot) tie-break reproduces the
    traversal's insertion order exactly).

    Returns ``(distances (R, k) f32, ids (R, k) i32)`` ascending-sorted
    per row with (+inf, -1) fill, same contract as `topk_l2`.
    """
    m, d = q.shape
    if m == 0 or cands.shape[1] == 0:
        return (
            jnp.full((m, k), jnp.inf, jnp.float32),
            jnp.full((m, k), -1, jnp.int32),
        )
    rb = jnp.broadcast_to(jnp.asarray(r, jnp.float32), (m,))
    sq, out_g, _slots = _leaf_call(
        q,
        jnp.asarray(cands, jnp.float32),
        None,
        cgids,
        radius_sq_upper(rb),
        k,
        bm,
        bn,
        bk,
        interpret,
    )
    dl = jnp.sqrt(sq)
    ok = dl <= rb[:, None]
    dd = jnp.where(ok, dl, jnp.inf)
    gg = jnp.where(ok, out_g, -1)
    return dd, gg


@functools.partial(
    jax.jit, static_argnames=("k", "bm", "bn", "bk", "interpret")
)
def leaf_topk_l2_raw(
    q: jax.Array,       # (R, D) one row per (segment, query) pair
    cands: jax.Array,   # (R, C, D) candidates in STORAGE dtype
    cgids: jax.Array,   # (R, C) i32 ids; negative = hole / dead slot
    r,                  # scalar or (R,) euclidean gate, PRE-widened
    k: int,
    *,
    cscale: jax.Array | None = None,  # (R, C) f32 int8 dequant scales
    bm: int = 8,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = False,
):
    """Raw selection pass over possibly-quantized candidates: streams
    `cands` at its storage width (f32 / bf16 / int8 + `cscale`), keeps
    the k smallest by the (squared distance, slot) key, and returns the
    UNREFINED ``(squared (R, k) f32, gids (R, k) i32, slots (R, k)
    i32)`` triple — squared distances of the *dequantized* coordinates,
    no sqrt, no exact radius mask. The caller over-fetches (k = k′ =
    k + slack), rescores the surviving slots against the f32 rows, and
    applies the exact gate there. `r` must already include the
    quantization error bound (the wrapper squares it conservatively via
    `radius_sq_upper`), so no true in-radius neighbor can fail the
    in-kernel gate."""
    m, d = q.shape
    if m == 0 or cands.shape[1] == 0:
        return (
            jnp.full((m, k), jnp.inf, jnp.float32),
            jnp.full((m, k), -1, jnp.int32),
            jnp.full((m, k), _I32_MAX, jnp.int32),
        )
    rb = jnp.broadcast_to(jnp.asarray(r, jnp.float32), (m,))
    return _leaf_call(
        q, cands, cscale, cgids, radius_sq_upper(rb), k, bm, bn, bk,
        interpret,
    )
