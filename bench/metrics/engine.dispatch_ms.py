"""Mean host time of one engine dispatch (the ``engine.dispatch`` span:
phase 1, the frontier-width sync, phase 2, the certificate sync, any
f32 rerun), in ms, over the window."""
from bench import readers


def read(ctx):
    return readers.histogram_mean(ctx, "span.engine.dispatch", 1e3)
