"""Seconds spent building programs, from process start to the window's
end: the sum of every ``jit.trace_s``, ``jit.lower_s`` and
``jit.compile_s`` histogram (tracing, lowering, backend compile or
cache load, by function) in the registry at the window's end. Each
event counts its own seconds less those nested in it, so the sum is
thread-seconds; builds on parallel threads add up."""

PREFIXES = ("jit.trace_s", "jit.lower_s", "jit.compile_s")


def read(ctx):
    sums = [h["sum"] for key, h in ctx.obs1["histograms"].items()
            if key.split("{", 1)[0] in PREFIXES]
    return sum(sums) if sums else None
