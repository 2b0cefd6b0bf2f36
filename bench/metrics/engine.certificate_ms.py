"""Mean host time of the quantized containment certificate (the
``engine.certificate`` span, its three readbacks included), in ms, over
the window."""
from bench import readers


def read(ctx):
    return readers.histogram_mean(ctx, "span.engine.certificate", 1e3)
