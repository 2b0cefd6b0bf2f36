"""Share of quantized dispatches rerun in f32 (``quantized.rescore``
fallback over exact + fallback), in %, over the window."""
from bench import readers


def read(ctx):
    return readers.f32_fallback_pct(ctx)
