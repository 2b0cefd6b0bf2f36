"""Arithmetic shared by the per-layer metric readers in ``metrics/``.

Each reader gets the harness's context: ``counter(key)`` and
``histogram(key)`` give window deltas of the program's obs registry,
``trace`` the reduced device trace (``trace_reduce.py``), ``device``
the device record and ``cell`` the cell. A reader that finds nothing
to read returns None, and the harness leaves the metric out.
"""
from __future__ import annotations

from . import trace_reduce, work


def histogram_mean(ctx, key: str, scale: float = 1.0):
    count, total = ctx.histogram(key)
    return total / count * scale if count else None


def f32_fallback_pct(ctx):
    """Share of quantized phase-2 dispatches whose containment
    certificate failed, so that phase 2 ran again in f32."""
    exact = ctx.counter("quantized.rescore{result=exact}")
    fallback = ctx.counter("quantized.rescore{result=fallback}")
    total = exact + fallback
    return 100.0 * fallback / total if total else None


def idle_pct(ctx):
    t = ctx.trace
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def call_work(call: dict, d: int):
    """Logical work of one leaf-kernel call from its HLO shapes: the
    results come first (the k-best triple, (R, k) each), then the
    operands, among them the (R, C, padded d) candidates; a second
    (R, C) f32 operand beside the ids is a dequantisation scale."""
    shapes = call["shapes"]
    cand = next((s for s in shapes if len(s[1]) == 3), None)
    if cand is None or not shapes or len(shapes[0][1]) != 2:
        return None
    dtype, (rows, cands, _) = cand
    k = shapes[0][1][1]
    per_row = [s for s in shapes if tuple(s[1]) == (rows, cands)]
    return work.leaf_topk_l2(rows, cands, d, trace_reduce.ITEMSIZE[dtype], k,
                             scaled=len(per_row) >= 2)


def roofline_pct(ctx, scope: str):
    """Least time the logical work of the kernel calls traced under the
    named scope ``scope`` needs at the chip's peaks, over the device
    time those calls took."""
    calls = (ctx.trace or {}).get("kernels", {}).get(scope)
    if not calls:
        return None
    peak = work.peaks(ctx.device["kind"])
    d = int(ctx.cell.config["dim"])
    least = spent = 0.0
    for call in calls:
        w = call_work(call, d)
        if w is None:
            return None
        least += work.least_seconds(w, peak)[0]
        spent += call["seconds"]
    return 100.0 * least / spent if spent else None
