"""Kernel microbenchmarks + analytic TPU roofline for the Pallas
kernels. On CPU the kernels execute in interpret mode (Python), so
wall-clock here measures the jnp oracle (what XLA:CPU runs); the TPU
numbers are analytic roofline terms from each kernel's exact FLOP/byte
counts (v5e: 197 TFLOP/s bf16, 819 GB/s HBM).

The headline comparison is fused vs unfused top-k: the unfused path
(pairwise kernel + row argsort) writes the (Q, N) distance matrix to
HBM and reads it back to sort, so its memory time scales with Q·N; the
fused kernel (`topk_l2.py`) streams `p` once and emits only (Q, k), so
its memory time is the irreducible input read. Both paths run the same
MXU matmul, which is why the fused kernel flips from memory- to
compute-bound once Q·N dwarfs the input — exactly the regime where the
unfused path is stuck on the writeback.

Shapes are capped by the BENCH_N / BENCH_Q env overrides (CI smoke leg)
like every other section.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.kernels import autotune as _at
from repro.kernels import ops, ref
from repro.kernels import pairwise_l2 as _pw
from repro.kernels import topk_l2 as _tk

from .common import emit, env_caps, radius_for, timed, write_bench_json

# the analytic terms are v5e terms, read from the autotuner's chip table
PEAK_FLOPS = _at.CHIPS["TPU v5 lite"]["peak_flops"]
HBM_BW = _at.CHIPS["TPU v5 lite"]["hbm_bw"]


def _capped(m: int, n: int):
    n_cap, q_cap = env_caps()
    return (min(m, q_cap) if q_cap else m, min(n, n_cap) if n_cap else n)


def run(full: bool = False):
    rng = np.random.default_rng(0)
    shapes = [_capped(512, 2048) + (64,), _capped(1024, 4096) + (128,)]
    for m, n, d in shapes:
        q = jnp.asarray(rng.standard_normal((m, d)), jnp.bfloat16)
        p = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
        # oracle wall time (XLA:CPU) — correctness-path throughput
        fn = lambda: ref.pairwise_sq_l2(q, p).block_until_ready()
        fn()
        _, dt = timed(fn, repeat=3)
        # same analytic terms the wrapper accounting bills per call
        plan = _pw.block_plan(m, n, d, itemsize=2)  # bf16 inputs
        t_comp = plan["flops"] / PEAK_FLOPS
        t_mem = plan["hbm_bytes"] / HBM_BW
        emit(
            f"kernel/pairwise_l2/{m}x{n}x{d}",
            dt * 1e6,
            f"cpu_ref_us;tpu_compute_us={t_comp * 1e6:.1f};"
            f"tpu_memory_us={t_mem * 1e6:.1f};"
            f"bound={'compute' if t_comp > t_mem else 'memory'}",
        )
    # ---- fused streaming top-k vs the unfused materialize+argsort path ----
    for m, n, d in shapes:
        q = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
        p = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        gids = np.arange(n, dtype=np.int32)
        gids[::13] = -1  # some dead slots so the liveness gate is live
        g = jnp.asarray(gids)
        for k in (8, 64):
            # unfused wall time (XLA:CPU oracle): materialize + argsort
            fn = lambda: ref.topk_l2(q, p, g, np.inf, k)[0].block_until_ready()
            fn()
            _, dt = timed(fn, repeat=3)
            # HBM traffic: both paths read q, p, gids and write (Q, k);
            # the unfused path additionally writes the (Q, N) matrix and
            # reads it back for the row sort. The fused side's bytes and
            # FLOPs (matmul + selection network) come from the kernel's
            # own block_plan — the same terms ops.py bills per call
            plan = _tk.block_plan(m, n, d, k)
            bytes_fused = plan["hbm_bytes"]
            bytes_unfused = bytes_fused + 2 * m * n * 4
            t_mem_f = bytes_fused / HBM_BW
            t_mem_u = bytes_unfused / HBM_BW
            t_comp_f = plan["flops"] / PEAK_FLOPS
            emit(
                f"kernel/topk_l2/{m}x{n}x{d}/k={k}",
                dt * 1e6,
                "cpu_unfused_ref_us;"
                f"tpu_fused_mem_us={t_mem_f * 1e6:.1f};"
                f"tpu_fused_compute_us={t_comp_f * 1e6:.1f};"
                f"tpu_unfused_mem_us={t_mem_u * 1e6:.1f};"
                f"hbm_bytes_fused={bytes_fused};"
                f"hbm_bytes_unfused={bytes_unfused};"
                f"hbm_reduction={bytes_unfused / bytes_fused:.1f}x;"
                f"fused_bound="
                f"{'compute' if t_comp_f > t_mem_f else 'memory'};"
                "unfused_bound=memory",
            )
    for n, d in [(200_000, 2), (100_000, 64)]:
        _, n = _capped(0, n)
        x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        mean = x.mean(0)
        w = jnp.asarray(rng.standard_normal(d), jnp.float32)
        fn = lambda: ref.cov_matvec(x, mean, w).block_until_ready()
        fn()
        _, dt = timed(fn, repeat=3)
        flops = 4 * n * d  # two matvecs
        bytes_ = n * d * 4  # single streaming read (fused)
        emit(
            f"kernel/cov_matvec/{n}x{d}",
            dt * 1e6,
            f"cpu_ref_us;tpu_memory_us={bytes_ / HBM_BW * 1e6:.1f};"
            f"ai={flops / bytes_:.2f}flops_per_byte;bound=memory",
        )
    # ---- fused two-phase traversal vs the classic in-loop jnp leaves ----
    # Same tree, same queries, both paths bit-exact: phase 1 collects the
    # pruned leaf frontier, phase 2 evaluates the gathered candidates
    # with the leaf_topk_l2 kernel instead of evaluating every leaf
    # inside the traversal loop.
    import jax

    from repro.core import build_host as _bh
    from repro.core import search_jax as _sj
    from repro.query import shapes as _shapes

    m, n = _capped(64, 8192)
    d, k = 16, 8
    pts = rng.standard_normal((n, d)).astype(np.float32)
    tree = _bh.build(pts)
    dts = jax.tree_util.tree_map(
        lambda x: x[None], _sj.device_tree(tree)
    )
    tgids = jnp.arange(tree.n_points, dtype=jnp.int32)[None]
    qs = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    rb = jnp.full((m,), jnp.float32(radius_for(pts, frac=0.25)))
    ss = _shapes.padded_stack_size(_sj.max_depth(tree))

    def _classic():
        return jax.block_until_ready(
            _sj.constrained_knn_stacked(dts, tgids, qs, rb, k, ss).distances
        )

    _classic()  # compile
    _, dt_c = timed(_classic, repeat=3)

    # cap = total leaf count, so the frontier can never overflow and the
    # timing below always measures the fused path, not the fallback
    fcap = int((np.asarray(tree.leaf_of_node) >= 0).sum())

    def _fused():
        res = _sj.constrained_knn_stacked_fused(
            dts, tgids, qs, rb, k, ss, frontier_cap=fcap
        )
        return jax.block_until_ready(res.distances) if res is not None else None

    if _fused() is None:  # frontier-cap overflow: record, skip timing
        emit(
            f"traversal/fused/{n}x{m}/k={k}",
            dt_c * 1e6,
            "frontier_overflow_fell_back_to_jnp_leaf",
        )
    else:
        _, dt_f = timed(_fused, repeat=3)
        # on CPU the leaf kernel runs in interpret mode (Python), so the
        # wall ratio here tracks correctness-path overhead, not the TPU
        # speedup — the TPU story is the analytic plan rows below
        emit(
            f"traversal/fused/{n}x{m}/k={k}",
            dt_f * 1e6,
            f"cpu_interpret_wall;jnp_leaf_us={dt_c * 1e6:.1f};"
            f"wall_ratio_vs_jnp_leaf={dt_c / dt_f:.2f}x",
        )

    # ---- autotuner: analytic choice, then measured refinement ----------
    mm, nn = _capped(256, 4096)
    dd, kk = 64, 8
    qa = jnp.asarray(rng.standard_normal((mm, dd)), jnp.float32)
    pa = jnp.asarray(rng.standard_normal((nn, dd)), jnp.float32)
    ga = jnp.arange(nn, dtype=jnp.int32)

    def _measure(plan):
        return _at.timed(
            lambda: ops.topk_l2(
                qa, pa, ga, np.inf, kk,
                bm=plan["bm"], bn=plan["bn"], bk=plan["bk"],
            )
        )

    plan = _at.choose_plan(
        "topk_l2", mm, nn, dd, kk, measure=_measure, trials=2
    )
    emit(
        f"autotune/topk_l2/{mm}x{nn}x{dd}/k={kk}",
        plan.get("measured_us", plan["score"] * 1e6),
        f"bm={plan['bm']};bn={plan['bn']};bk={plan['bk']};"
        f"blocks={plan['blocks']};pred_us={plan['score'] * 1e6:.1f};"
        f"source={plan['source']}",
        unit="us_per_call",
    )
    cc = 1024  # representative gathered-frontier width (F_eff × leaf)
    lplan = _at.choose_plan("leaf_topk_l2", m, cc, d, k)
    emit(
        f"autotune/leaf_topk_l2/{m}x{cc}x{d}/k={k}",
        lplan["score"] * 1e6,
        f"bm={lplan['bm']};bn={lplan['bn']};bk={lplan['bk']};"
        f"blocks={lplan['blocks']};source={lplan['source']}",
        unit="pred_us",
    )

    # ---- quantized segment storage: streamed HBM bytes vs all-f32 ------
    # The leaf kernel is input-read bound, so storage width IS the
    # roofline: compare the analytic stream bytes of the f32 plan vs the
    # BENCH_DTYPE plan at a representative gathered-frontier shape, then
    # run the real raw kernel (interpret mode on CPU) against its
    # oracle so the numbers ship with a correctness check.
    from repro.kernels import quantize as _qz

    from .common import storage_dtype as _storage_dtype

    sdt = _storage_dtype()
    rq, cq_, dq, kq = _capped(64, 1024) + (128, 8)
    plan_f32 = _tk.leaf_block_plan(rq, cq_, dq, kq, itemsize=4)
    plan_q = _tk.leaf_block_plan(
        rq, cq_, dq, kq, itemsize=_qz.itemsize_of(sdt)
    )
    reduction = plan_f32["stream_bytes"] / plan_q["stream_bytes"]
    if sdt == "bfloat16" and reduction < 1.9:
        raise AssertionError(
            f"bf16 quantized stream reduction {reduction:.2f}x < 1.9x "
            f"({plan_f32['stream_bytes']} -> {plan_q['stream_bytes']} B)"
        )
    lq_pts = rng.standard_normal((rq, cq_, dq)).astype(np.float32)
    lq_q, lq_scale, lq_err = _qz.quantize_leaves(lq_pts, sdt)
    if lq_q is None:  # BENCH_DTYPE=float32: stream the f32 buffer itself
        lq_q = jnp.asarray(lq_pts)
    qrows = jnp.asarray(rng.standard_normal((rq, dq)), jnp.float32)
    cgq = jnp.asarray(
        np.where(rng.random((rq, cq_)) < 0.1, -1, np.arange(cq_)[None, :]),
        jnp.int32,
    )
    rbq = jnp.full((rq,), jnp.inf, jnp.float32)

    def _quant():
        return jax.block_until_ready(
            ops.leaf_topk_l2_raw(qrows, lq_q, cgq, rbq, kq, cscale=lq_scale)[0]
        )

    _quant()  # compile
    _, dt_q = timed(_quant, repeat=3)
    emit(
        f"kernel/leaf_topk_raw/{rq}x{cq_}x{dq}/k={kq}/{sdt}",
        dt_q * 1e6,
        f"cpu_interpret_wall;storage_dtype={sdt};"
        f"stream_bytes_f32={plan_f32['stream_bytes']};"
        f"stream_bytes_{sdt}={plan_q['stream_bytes']};"
        f"stream_reduction={reduction:.2f}x;qerr={lq_err:.3e};"
        f"tpu_mem_us_f32={plan_f32['stream_bytes'] / HBM_BW * 1e6:.1f};"
        f"tpu_mem_us_{sdt}={plan_q['stream_bytes'] / HBM_BW * 1e6:.1f}",
    )
    sq_k, g_k, s_k = ops.leaf_topk_l2_raw(
        qrows, lq_q, cgq, rbq, kq, cscale=lq_scale
    )
    sq_r, g_r, s_r = ref.leaf_topk_l2_raw(
        qrows, lq_q, cgq, rbq, kq, cscale=lq_scale
    )
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_r))
    np.testing.assert_array_equal(np.asarray(g_k), np.asarray(g_r))
    # int8 dequant may FMA-contract differently in-kernel: ulp tolerance
    np.testing.assert_allclose(
        np.asarray(sq_k), np.asarray(sq_r), rtol=1e-5, atol=0
    )
    emit(
        f"kernel/leaf_topk_raw_check/{sdt}", 0.0, "quantized_vs_oracle_ok"
    )

    # interpret-mode correctness spot checks ride along: the REAL Pallas
    # programs (pairwise + fused top-k) vs their oracles
    q = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    p = jnp.asarray(rng.standard_normal((96, 32)), jnp.float32)
    np.testing.assert_allclose(
        ops.pairwise_sq_l2(q, p), ref.pairwise_sq_l2(q, p), rtol=1e-4, atol=1e-4
    )
    emit("kernel/interpret_check", 0.0, "allclose_ok")
    g = jnp.asarray(
        np.where(rng.random(96) < 0.2, -1, np.arange(96)), jnp.int32
    )
    fd, fi = ops.topk_l2(q, p, g, 5.0, 8)
    rd, ri = ref.topk_l2(q, p, g, 5.0, 8)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(ri))
    np.testing.assert_allclose(fd, rd, rtol=1e-5, atol=1e-6)
    emit("kernel/topk_interpret_check", 0.0, "bit_identical_order_ok")


if __name__ == "__main__":
    run()
    write_bench_json("kernels")
