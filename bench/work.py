"""Peaks by device kind, and the logical work of one leaf-kernel call.

The work is what the algorithm needs, from logical shapes: R query rows,
each scanning its own C gathered candidates of d coordinates (d from the
configuration, never the 128-lane padding the kernel launches with), at
the candidates' storage item size. So a change that removes padding
raises the roofline share; nothing here reads the program's own
accounting (``block_plan``) or its peaks table.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = _PEAKS) -> dict:
    """The peaks row of ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def leaf_topk_l2(rows: int, cands: int, d: int, itemsize: int, k: int,
                 scaled: bool = False) -> dict:
    """FLOPs and HBM bytes one call of the phase-2 leaf kernel needs.

    FLOPs: a subtract, a multiply and an add per coordinate of every
    candidate (3 R C d), and one multiply more per coordinate when the
    candidates carry a dequantisation scale. Bytes: the candidates
    (R C d at ``itemsize``), their int32 ids (R C 4) and scales (R C 4,
    when scaled), the f32 query rows (R d 4) and radii (R 4), and the
    k-best triple written back (distance, id, slot: R k 12). The
    selection network is not counted: it is the kernel's way of keeping
    the k best, not work the answer needs.
    """
    flops = 3 * rows * cands * d + (rows * cands * d if scaled else 0)
    hbm = (rows * cands * d * itemsize + rows * cands * 4
           + (rows * cands * 4 if scaled else 0)
           + rows * d * 4 + rows * 4 + rows * k * 12)
    return {"flops": flops, "hbm_bytes": hbm}


def least_seconds(work: dict, peak: dict) -> tuple:
    """(seconds, bound): the least time the chip could take for
    ``work``, and whether FLOP/s or bytes/s bounds it."""
    t_flops = work["flops"] / peak["flops_per_s"]
    t_bytes = work["hbm_bytes"] / peak["hbm_bytes_per_s"]
    return (t_bytes, "hbm_bytes") if t_bytes >= t_flops else (t_flops, "flops")
