"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of device op intervals) / window."""
from bench import readers


def read(ctx):
    return readers.idle_pct(ctx)
