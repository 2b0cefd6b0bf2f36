"""Reduce one profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU each
chip is a plane ``/device:TPU:<n>`` whose line ``XLA Modules`` holds one
event per program run (named ``<jit name>(<fingerprint>)``) and whose
line ``XLA Ops`` holds one event per operation run, named by its HLO
text (``%custom-call.3 = (f32[64,16]{...}, ...) custom-call(bf16[...]
...)``): results first, then operands, with their shapes. Host spans
(``jax.profiler.TraceAnnotation``, which every ``obs.span`` of the
program opens) are events of the plane ``/host:CPU``, on the same clock.

From the trace, all clipped to the host annotation that marks the
window (``bench.window``):

* ``busy_s``: the union of the program runs' intervals, averaged over
  the chips; ``window_s``: the window's length;
* ``device_ops``: device seconds by program (jit name), top ten;
* ``kernels``: each Pallas kernel call (a ``custom-call`` op) with its
  device seconds and HLO shapes, keyed by the op's name without its
  ``.<n>`` suffix: XLA names the op after the ``jax.named_scope`` it
  was traced in, so ``kernel.leaf_topk_l2`` holds both phase-2
  variants (the op is ``%kernel.leaf_topk_l2.1 = (...) custom-call(...)``);
* ``idle_gaps``: idle seconds inside the window by the innermost host
  span open at each gap's middle (``engine.dispatch``, ...), top ten.
"""
from __future__ import annotations

import collections
import glob
import heapq
import os
import re

DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIXES = ("engine.", "serve.", "index.", "bench.")
CUSTOM_CALL = " custom-call("
_OP_NAME = re.compile(r"^%([^ ]+?)(\.\d+)? = ")
_SHAPE = re.compile(r"\b(pred|bf16|f16|f32|f64|s8|s16|s32|s64|u8|u32)\[([0-9,]*)\]")
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
            "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}
NO_SPAN = "outside program spans"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def program(module_event_name: str) -> str:
    """``jit_foo(123)`` -> ``jit_foo``."""
    return module_event_name.split("(", 1)[0]


def shapes(hlo: str) -> list:
    """[(dtype, [dims])] of every ``dtype[d0,d1,...]`` in the HLO text
    of one op, in order: the results first, then the operands."""
    return [(m.group(1), [int(x) for x in m.group(2).split(",") if x])
            for m in _SHAPE.finditer(hlo)]


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _innermost(spans, gaps) -> list:
    """For each (start, end) gap, in order, the name of the shortest
    host span open at its middle, or ``NO_SPAN``."""
    spans = sorted(spans)
    active, out, i = [], [], 0
    for s, e in gaps:
        mid = (s + e) / 2
        while i < len(spans) and spans[i][0] <= mid:
            heapq.heappush(active, (spans[i][1], spans[i][0], spans[i][2]))
            i += 1
        while active and active[0][0] <= mid:
            heapq.heappop(active)
        live = [a for a in active if a[0] > mid]
        out.append(min(live, key=lambda a: a[0] - a[1])[2] if live else NO_SPAN)
    return out


def _line(plane, name):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def reduce_space(space, window: str = "bench.window") -> dict:
    """Reduce a ``ProfileData``; times in seconds."""
    host = space.find_plane_with_name(HOST_PLANE)
    if host is None:
        raise ValueError("trace has no host plane")
    spans, win = [], None
    for line in host.lines:
        for ev in line.events:
            name = ev.name
            if name == window:
                win = (ev.start_ns, ev.end_ns)
            elif name.startswith(SPAN_PREFIXES):
                spans.append((ev.start_ns, ev.end_ns, name))
    if win is None:
        raise ValueError(f"trace has no host annotation {window!r}")
    lo, hi = win
    devices = [p for p in space.planes if p.name.startswith(DEVICE_PREFIX)]
    if not devices:
        raise ValueError("trace has no TPU device plane")

    busy_total = 0.0
    by_program = collections.Counter()
    kernels = collections.defaultdict(list)
    gaps = collections.Counter()
    for plane in devices:
        runs = []
        mods = _line(plane, MODULES_LINE)
        for ev in (mods.events if mods is not None else ()):
            s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
            if e > s:
                runs.append((s, e, program(ev.name)))
        runs.sort()
        for s, e, prog in runs:
            by_program[prog] += (e - s) * 1e-9
        busy = _union((s, e) for s, e, _ in runs)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        for (s, e), label in zip(idle, _innermost(spans, idle)):
            gaps[label] += (e - s) * 1e-9
        ops = _line(plane, OPS_LINE)
        for ev in (ops.events if ops is not None else ()):
            name = ev.name
            if CUSTOM_CALL not in name:
                continue
            s, e = ev.start_ns, ev.end_ns
            if s < lo or e > hi:
                continue
            m = _OP_NAME.match(name)
            text = name.split(", custom_call_target")[0]
            kernels[m.group(1) if m else "?"].append(
                {"seconds": (e - s) * 1e-9, "shapes": shapes(text)})
    n = len(devices)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / n,
        "kernels": dict(kernels),
        "device_ops": [[k, v / n] for k, v in by_program.most_common(TOP)],
        "idle_gaps": [[k, v / n] for k, v in gaps.most_common(TOP)],
    }


def reduce_file(path: str, window: str = "bench.window") -> dict:
    from jax.profiler import ProfileData

    return reduce_space(ProfileData.from_file(path), window)
