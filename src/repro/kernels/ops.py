"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels run compiled; on the CPU (the test suite) they run
in interpret mode, which executes the kernel body in Python — the same
program the TPU compiles. Any other backend is an error: there is no
silent interpret fallback on an accelerator.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs

from . import pairwise_l2 as _pw
from . import cov_matvec as _cm
from . import topk_l2 as _tk


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; "
        f"backend {backend!r} is neither"
    )


def _tuned(
    kernel: str,
    m: int,
    n: int,
    d: int,
    k: int,
    kw: dict,
    dtype: str = "float32",
):
    """Fill the block sizes the caller did NOT pin with the autotuner's
    choice (an explicit bm/bn/bk always wins, per key — e.g. the fused
    traversal pins bk for exactness and lets bm/bn tune). `dtype` is
    the STORAGE dtype of the streamed buffer: it keys the cache and
    sets the planner's itemsize, so bf16/int8 streams rank blocks by
    their true bytes. Returns the chosen plan, or None when nothing
    needed tuning."""
    missing = [b for b in ("bm", "bn", "bk") if b not in kw]
    if not (m and n) or not missing:
        return None
    from . import autotune as _at  # lazy: autotune imports the planners

    plan = _at.choose_plan(kernel, m, n, d, k, dtype=dtype)
    for b in missing:
        kw[b] = plan[b]
    return plan


def _blocks(kw: dict) -> dict:
    return {b: kw[b] for b in ("bm", "bn", "bk") if b in kw}


def _account(kernel: str, plan: dict) -> None:
    """Bill one launch to the registry: calls, analytic HBM bytes, and
    FLOPs per kernel — the inputs of the roofline report."""
    reg = obs.REGISTRY
    reg.counter("kernel.calls", kernel=kernel).inc()
    reg.counter("kernel.hbm_bytes", kernel=kernel).inc(plan["hbm_bytes"])
    reg.counter("kernel.flops", kernel=kernel).inc(plan["flops"])
    reg.counter("kernel.blocks", kernel=kernel).inc(plan["blocks"])


def _concrete(*arrays) -> bool:
    """True when the wrapper runs eagerly (host call time). Inside a
    trace (e.g. cov_matvec under `lax.fori_loop`) the inputs are
    Tracers and a per-call count would be wrong — one trace, many
    executions — so accounting is skipped."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def pairwise_sq_l2(q, p, **kw):
    """Blocked squared-L2 distance matrix (M, N) f32."""
    kw.setdefault("interpret", _interpret())
    m, d = q.shape
    n = p.shape[0]
    _tuned("pairwise_sq_l2", m, n, d, 0, kw)
    if obs.REGISTRY.enabled and _concrete(q, p):
        _account(
            "pairwise_sq_l2",
            _pw.block_plan(
                m, n, d,
                itemsize=jnp.dtype(q.dtype).itemsize,
                **_blocks(kw),
            ),
        )
    return _pw.pairwise_sq_l2(q, p, **kw)


def pairwise_l2(q, p, **kw):
    """Euclidean distance matrix (M, N) f32."""
    return jnp.sqrt(pairwise_sq_l2(q, p, **kw))


def topk_l2(q, p, gids, r, k, **kw):
    """Fused streaming constrained top-k: (Q, k) ascending (dist, gid)
    without ever materializing the (Q, N) distance matrix."""
    kw.setdefault("interpret", _interpret())
    m, d = q.shape
    n = p.shape[0]
    _tuned("topk_l2", m, n, d, k, kw)
    if obs.REGISTRY.enabled and _concrete(q, p, gids) and m and n:
        _account("topk_l2", _tk.block_plan(m, n, d, k, **_blocks(kw)))
    return _tk.topk_l2(q, p, gids, r, k, **kw)


def leaf_topk_l2(q, cands, cgids, r, k, **kw):
    """Batched-candidates fused top-k: each query row scans its own
    (C, D) candidate matrix — the phase-2 evaluator of the two-phase
    traversal. Interpret mode on CPU runs the REAL kernel body, so
    tier-1 exercises the exact program the TPU compiles."""
    kw.setdefault("interpret", _interpret())
    m, d = q.shape
    c = cands.shape[1]
    _tuned("leaf_topk_l2", m, c, d, k, kw)
    if obs.REGISTRY.enabled and _concrete(q, cands, cgids) and m and c:
        _account(
            "leaf_topk_l2", _tk.leaf_block_plan(m, c, d, k, **_blocks(kw))
        )
    return _tk.leaf_topk_l2(q, cands, cgids, r, k, **kw)


def leaf_topk_l2_raw(q, cands, cgids, r, k, cscale=None, **kw):
    """Quantized-storage selection pass: streams `cands` at its storage
    width (f32 / bf16 / int8 + per-candidate `cscale`) and returns the
    raw (squared, gid, slot) k-best per row — the over-fetch half of
    the quantized read path; `core/search_jax` rescores the surviving
    slots in f32. Bills HBM bytes at the TRUE storage width and tracks
    the f32-equivalent bytes the quantized stream avoided, feeding the
    obs `quantized` section."""
    kw.setdefault("interpret", _interpret())
    m, d = q.shape
    c = cands.shape[1]
    sdt = str(jnp.dtype(cands.dtype))
    _tuned("leaf_topk_l2", m, c, d, k, kw, dtype=sdt)
    if obs.REGISTRY.enabled and _concrete(q, cands, cgids) and m and c:
        itemsize = jnp.dtype(cands.dtype).itemsize
        plan = _tk.leaf_block_plan(
            m, c, d, k, itemsize=itemsize, **_blocks(kw)
        )
        _account("leaf_topk_l2_raw", plan)
        # quantized-vs-f32 stream accounting: what this launch streamed
        # at storage width vs what the same launch would have at f32
        f32_plan = _tk.leaf_block_plan(m, c, d, k, **_blocks(kw))
        reg = obs.REGISTRY
        reg.counter("quantized.stream_bytes", dtype=sdt).inc(
            plan["stream_bytes"]
        )
        reg.counter("quantized.f32_stream_bytes", dtype=sdt).inc(
            f32_plan["stream_bytes"]
        )
    return _tk.leaf_topk_l2_raw(q, cands, cgids, r, k, cscale=cscale, **kw)


def lower_bounds(q, centers, radii, **kw):
    """Ball lower bounds max(0, ||q-c|| - radius): the pruning quantity
    D_N of the paper's search (§4.2), batched over queries × nodes."""
    d = pairwise_l2(q, centers, **kw)
    return jnp.maximum(d - radii[None, :], 0.0)


def cov_matvec(x, mean, w, **kw):
    """Fused centered-covariance matvec (one power-iteration step)."""
    kw.setdefault("interpret", _interpret())
    if obs.REGISTRY.enabled and _concrete(x, mean, w):
        n, d = x.shape
        _account(
            "cov_matvec",
            # two matvecs over one streaming read of x; no blocking
            # geometry to resolve, so the plan is the formulas alone
            {"flops": 4 * n * d, "hbm_bytes": n * d * 4, "blocks": 1},
        )
    return _cm.cov_matvec(x, mean, w, **kw)


def power_iteration(x, iters: int = 16, **kw):
    """First principal component of x (N, D) using the fused kernel."""
    n, d = x.shape
    mean = x.mean(axis=0)
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (d,), jnp.float32)
    w = w / jnp.linalg.norm(w)

    def body(_, w):
        v = cov_matvec(x, mean, w, **kw)
        nrm = jnp.linalg.norm(v)
        return jnp.where(nrm > 1e-12, v / jnp.maximum(nrm, 1e-30), w)

    return jax.lax.fori_loop(0, iters, body, w)
