"""Shape-aware block-size autotuner for the fused kernels.

The kernels (`topk_l2`, `leaf_topk_l2`, `pairwise_sq_l2`) take static
(bm, bn, bk) block sizes, and until now every call used one hardwired
default. This module chooses per shape-class instead: candidate pow2
plans are resolved through each kernel's `block_plan()` (the single
source of truth for clamp logic and analytic cost) and ranked by a
roofline objective over the BLOCK-DEPENDENT terms —

    score = max(padded_flops / peak_flops, stream_bytes / hbm_bw)
            + blocks * launch_overhead_s

i.e. padding waste, pipeline refetch traffic, and per-block launch
overhead; plans whose VMEM working set cannot double-buffer inside the
budget are rejected outright. The envelope comes from `CHIPS`, one row
per `device_kind`. Winners are cached per
(kernel, shape-class, k, dtype, device kind), where the shape class is the
same pow2 bucketing the query engine pads to — so a shape class
resolves to ONE stable plan and jit never recompiles for block-size
churn.

Ranking is analytic by default (zero kernel launches). `choose_plan`
can optionally *measure*: time the top candidates for real and keep
the fastest, recording predicted-vs-measured to the obs registry —
benchmarks opt in, hot paths never do.

`REPRO_BLOCK_PLAN=<bq>x<bn>` (optionally `<bq>x<bn>x<bk>`) pins every
decision to one plan, validated against `block_plan()`'s constraints —
the bisection escape hatch when a tuned plan regresses.
"""
from __future__ import annotations

import os
import time
from typing import Callable, Optional

from repro import obs

from . import pairwise_l2 as _pw
from . import topk_l2 as _tk

# Single-chip envelope of the analytic ranking, one row per
# `jax.Device.device_kind`. "TPU v5 lite" is the TPU v5e: 197 TFLOP/s
# bf16 and 819 GB/s HBM (Google Cloud documentation, "TPU v5e");
# 16 MiB is Mosaic's default scoped-VMEM limit on v5e (JAX Pallas TPU
# docs), halved here because every block is double-buffered. The
# per-block launch overhead is a modelling estimate, not a measurement.
CHIPS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,         # FLOP/s
        "hbm_bw": 819e9,              # bytes/s
        "launch_overhead_s": 2e-6,    # per grid block: issue + ramp
        "vmem_budget": 8 * 2**20,     # single-buffer working set
    },
}
# interpret mode on the CPU runs the program the TPU compiles, so it
# ranks blocks for the v5e
CPU_CHIP = "TPU v5 lite"

# candidate pow2 block sizes per kernel (resolved through block_plan,
# which clamps them to the problem shape, so oversize entries are safe)
_CANDIDATES = {
    "topk_l2": {
        "bm": (8, 32, 128, 256),
        "bn": (128, 256, 512),
        "bk": (128, 256, 512),
    },
    "leaf_topk_l2": {
        "bm": (8, 16, 32),
        "bn": (128, 256, 512),
        "bk": (128, 256, 512),
    },
    "pairwise_sq_l2": {
        "bm": (8, 32, 128, 256),
        "bn": (128, 256, 512),
        "bk": (128, 256, 512),
    },
}

# every planner takes `itemsize` (the streamed buffer's storage width):
# quantized streams rank candidate blocks by their TRUE byte traffic
# and size VMEM for the narrow buffer they actually hold
_PLANNERS: dict[str, Callable[..., dict]] = {
    "topk_l2": lambda m, n, d, k, **bw: _tk.block_plan(m, n, d, k, **bw),
    "leaf_topk_l2": lambda m, n, d, k, **bw: _tk.leaf_block_plan(
        m, n, d, k, **bw
    ),
    "pairwise_sq_l2": lambda m, n, d, k, **bw: _pw.block_plan(
        m, n, d, **bw
    ),
}

_CACHE: dict[tuple, dict] = {}


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def shape_class(m: int, n: int, d: int) -> tuple:
    """The pow2 bucket a problem shape tunes under — the same padding
    classes the query engine stacks segments by, so one engine shape
    class always resolves to one cached plan."""
    return (_next_pow2(m), _next_pow2(n), _next_pow2(d))


def parse_block_plan_env(
    value: Optional[str] = None,
) -> Optional[tuple]:
    """Parse the `REPRO_BLOCK_PLAN=<bq>x<bn>[x<bk>]` pin. Returns
    (bm, bn, bk) with bk defaulted to 512, or None when unset.
    Raises ValueError on malformed values or sizes that violate the
    kernels' block constraints (pow2 bn for the selection network,
    bm a multiple of 8, all positive)."""
    if value is None:
        value = os.environ.get("REPRO_BLOCK_PLAN", "")
    if not value:
        return None
    parts = value.lower().split("x")
    if len(parts) not in (2, 3):
        raise ValueError(
            f"REPRO_BLOCK_PLAN must be <bq>x<bn> or <bq>x<bn>x<bk>, "
            f"got {value!r}"
        )
    try:
        nums = [int(p) for p in parts]
    except ValueError:
        raise ValueError(f"REPRO_BLOCK_PLAN has non-integer parts: {value!r}")
    bm, bn = nums[0], nums[1]
    bk = nums[2] if len(nums) == 3 else 512
    if bm <= 0 or bn <= 0 or bk <= 0:
        raise ValueError(f"REPRO_BLOCK_PLAN sizes must be positive: {value!r}")
    if bm % 8:
        raise ValueError(
            f"REPRO_BLOCK_PLAN bq must be a multiple of 8 (sublane), "
            f"got {bm}"
        )
    if bn & (bn - 1):
        raise ValueError(
            f"REPRO_BLOCK_PLAN bn must be a power of two (the in-kernel "
            f"selection network sorts along it), got {bn}"
        )
    if bk % 128:
        raise ValueError(
            f"REPRO_BLOCK_PLAN bk must be a multiple of 128 (lane), "
            f"got {bk}"
        )
    return bm, bn, bk


def device_kind() -> str:
    """The `CHIPS` row this process ranks for: the first device's kind
    on a TPU, the v5e row on the CPU (interpret mode). Any other
    backend raises — there is no generic envelope."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return CPU_CHIP
    if dev.platform == "tpu":
        return dev.device_kind
    raise RuntimeError(f"no kernel envelope for platform {dev.platform!r}")


def chip(kind: Optional[str] = None) -> dict:
    """The envelope row for `kind` (default: `device_kind()`). An
    unknown kind raises: ranking blocks against another chip's peaks
    would be silently wrong."""
    kind = device_kind() if kind is None else kind
    if kind not in CHIPS:
        raise ValueError(
            f"no envelope for device kind {kind!r}; add a sourced row "
            f"to autotune.CHIPS (known: {sorted(CHIPS)})"
        )
    return CHIPS[kind]


def score(plan: dict, env: dict) -> float:
    """Analytic roofline time of one launch under `plan` (seconds) on
    the chip `env`: compute/memory envelope of the padded work +
    per-block overhead."""
    t_comp = plan["padded_flops"] / env["peak_flops"]
    t_mem = plan["stream_bytes"] / env["hbm_bw"]
    return max(t_comp, t_mem) + plan["blocks"] * env["launch_overhead_s"]


def _rank(
    kernel: str,
    m: int,
    n: int,
    d: int,
    k: int,
    itemsize: int = 4,
    env: Optional[dict] = None,
) -> list[dict]:
    """All candidate plans for the shape, deduped post-clamp, feasible
    VMEM only, cheapest analytic score first."""
    env = chip() if env is None else env
    planner = _PLANNERS[kernel]
    cand = _CANDIDATES[kernel]
    seen, plans = set(), []
    for bm in cand["bm"]:
        for bn in cand["bn"]:
            for bk in cand["bk"]:
                p = planner(
                    m, n, d, k, bm=bm, bn=bn, bk=bk, itemsize=itemsize
                )
                key = (p["bm"], p["bn"], p["bk"])
                if key in seen:
                    continue
                seen.add(key)
                if 2 * p["vmem_bytes"] > env["vmem_budget"]:
                    continue
                p["score"] = score(p, env)
                plans.append(p)
    plans.sort(key=lambda p: p["score"])
    return plans


def _record(kernel: str, cls: tuple, k: int, plan: dict) -> None:
    """Publish the decision as labeled gauges + the exportable table."""
    if not obs.REGISTRY.enabled:
        return
    labels = {"kernel": kernel, "cls": "x".join(map(str, cls)), "k": k}
    g = obs.REGISTRY.gauge
    g("autotune.bm", **labels).set(plan["bm"])
    g("autotune.bn", **labels).set(plan["bn"])
    g("autotune.bk", **labels).set(plan["bk"])
    g("autotune.blocks", **labels).set(plan["blocks"])
    g("autotune.pred_us", **labels).set(plan["score"] * 1e6)
    if "measured_us" in plan:
        g("autotune.measured_us", **labels).set(plan["measured_us"])


def choose_plan(
    kernel: str,
    m: int,
    n: int,
    d: int,
    k: int = 0,
    *,
    dtype: str = "float32",
    kind: Optional[str] = None,
    measure: Optional[Callable[[dict], float]] = None,
    trials: int = 3,
) -> dict:
    """The (cached) block plan for one kernel launch shape.

    Cache key: (kernel, pow2 shape class, k, dtype, device kind) —
    `kind` defaults to `device_kind()`; every shape in a class gets the
    same plan, so the jit caches keyed on
    (shape, blocks) stay warm. `REPRO_BLOCK_PLAN` short-circuits the
    ranking entirely (source="env"). Passing `measure` (a callable
    running one launch under a candidate plan, returning seconds)
    re-ranks the top `trials` analytic candidates by wall clock and
    keeps the fastest (source="measured") — only benchmarks do this.
    """
    kind = device_kind() if kind is None else kind
    env = chip(kind)
    pinned = parse_block_plan_env()
    cls = shape_class(m, n, d)
    key = (kernel, cls, k, dtype, kind, pinned, measure is not None)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    import jax.numpy as jnp

    itemsize = jnp.dtype(dtype).itemsize
    if pinned is not None:
        bm, bn, bk = pinned
        plan = _PLANNERS[kernel](
            m, n, d, k, bm=bm, bn=bn, bk=bk, itemsize=itemsize
        )
        plan["score"] = score(plan, env)
        plan["source"] = "env"
    else:
        ranked = _rank(kernel, m, n, d, k, itemsize, env)
        plan = ranked[0]
        plan["source"] = "analytic"
        if measure is not None:
            best_t = None
            for cand in ranked[:trials]:
                t = min(measure(cand) for _ in range(2))
                cand["measured_us"] = t * 1e6
                if best_t is None or t < best_t:
                    best_t, plan = t, cand
            plan["source"] = "measured"
    _CACHE[key] = plan
    _record(kernel, cls, k, plan)
    return plan


def decisions() -> dict:
    """Every cached decision of this process, keyed for the
    `BENCH_obs.json` `autotune` section."""
    out = {}
    for (kernel, cls, k, dtype, kind, _pin, _meas), plan in _CACHE.items():
        key = f"{kernel}/{'x'.join(map(str, cls))}/k{k}/{dtype}/{kind}"
        out[key] = {
            "bm": plan["bm"],
            "bn": plan["bn"],
            "bk": plan["bk"],
            "grid": list(plan["grid"]),
            "blocks": plan["blocks"],
            "padded_flops": plan["padded_flops"],
            "stream_bytes": plan["stream_bytes"],
            "vmem_bytes": plan["vmem_bytes"],
            "pred_us": plan["score"] * 1e6,
            "source": plan["source"],
            **(
                {"measured_us": plan["measured_us"]}
                if "measured_us" in plan
                else {}
            ),
        }
    return out


def reset() -> None:
    """Drop all cached decisions (tests and benchmark isolation)."""
    _CACHE.clear()


def timed(fn: Callable[[], object]) -> float:
    """Wall-clock one launch (blocks on the result) — the `measure`
    building block used by the benchmark harness."""
    import jax

    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return time.perf_counter() - t0
