"""Mean host wait for phase 1 of a fused dispatch (the
``engine.frontier_sync`` span: one readback of the widest and the
total frontier), in ms, over the window."""
from bench import readers


def read(ctx):
    return readers.histogram_mean(ctx, "span.engine.frontier_sync", 1e3)
