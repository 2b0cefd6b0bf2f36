"""Roofline share of the phase-2 leaf kernel, in %: the Pallas calls
traced under the named scope ``kernel.leaf_topk_l2`` (both variants:
f32 ``leaf_topk_l2`` and bf16-storage ``leaf_topk_l2_raw``), whose least
time for their logical work at the peaks of ``bench/peaks.json`` is set
over their device time. Bytes bound it: 10 to 16 bytes per candidate
against 9 FLOPs (see ``bench/work.py``)."""
from bench import readers


def read(ctx):
    return readers.roofline_pct(ctx, "kernel.leaf_topk_l2")
