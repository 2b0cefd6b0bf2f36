"""The comparison that decides ``correct``.

Each sampled reply of the timed path is set beside the float64
reference (``reference.py``) rank by rank. Two numbers are compared,
the largest over all sampled replies and ranks, each as a share of the
reply's scale: the reference's farthest distance in that reply (its
k-th neighbour's, or its last within r). Dense data puts the k nearest
far inside r, so a share of r would let a wrong neighbour read as
rounding.

``dist_gap``  how far a reported distance lies from the reference's
              distance at the same rank;
``id_gap``    how far the true (float64) distance of the reported id
              lies from the reference's distance at that rank.

Ties in distance may be answered with either id, and then both gaps
stay at rounding size. A rank that one side fills and the other leaves
empty reads how far the filled distance lies inside (or outside) r, as
a share of r, so a point missed or admitted at the very edge of r reads as rounding and
one missed well inside r reads large. An id that names no point, or
that a reply repeats, reads 1. A third number, ``unanswered``, counts
the requests of the window that never resolved or resolved with an
error; its limit is 0.
"""
from __future__ import annotations

import numpy as np

from . import reference

SCALE_FLOOR = 1e-6      # least scale of a reply, as a share of r


def gaps(ids, dists, queries, points, k: int, r: float, ref=None):
    """Per-reply (dist_gap, id_gap) arrays of the replies
    ``ids``/``dists`` (m, k) against the reference. ``ref`` is the
    reference answer ``(ids, distances)`` when already computed."""
    ids = np.asarray(ids, np.int64).reshape(-1, k)
    dists = np.asarray(dists, np.float64).reshape(-1, k)
    if ref is None:
        ref = reference.constrained_knn(points, queries, k, r)
    _, ref_d = ref
    true_d = reference.distances_of(points, queries, ids)
    n = len(points)
    valid = ids >= 0
    ref_valid = np.isfinite(ref_d)
    both = valid & ref_valid
    only_prog = valid & ~ref_valid
    only_ref = ref_valid & ~valid

    # a rank both fill is measured against the reply's own scale (at
    # least a millionth of r: exact duplicates could make it 0); a rank
    # one side leaves empty, against r
    far = np.max(np.where(ref_valid, ref_d, 0.0), axis=1, keepdims=True)
    scale = np.broadcast_to(np.maximum(far, r * SCALE_FLOOR), ids.shape)

    def fill(rep):
        g = np.zeros(ids.shape)
        g[both] = np.abs(rep[both] - ref_d[both]) / scale[both]
        g[only_prog] = np.abs(rep[only_prog] - r) / r
        g[only_ref] = (r - ref_d[only_ref]) / r
        return g

    dist_gap = fill(dists)
    id_gap = fill(true_d)
    # ids that name no point, or repeat within one reply
    bad = valid & ((ids >= n) | ~np.isfinite(true_d))
    srt = np.sort(np.where(valid, ids, -1 - np.arange(k)), axis=1)
    dup = (srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)
    bad_rows = bad.any(axis=1) | dup.any(axis=1)
    id_gap[bad_rows] = 1.0
    dist_gap[~np.isfinite(dist_gap)] = 1.0
    return dist_gap.max(axis=1), id_gap.max(axis=1)


def judge(dist_gap, id_gap, unanswered: int, limits: dict) -> tuple:
    """(correct, checks): each number beside its limit, in a fixed
    order. An empty sample compares nothing and is not correct."""
    checks = {
        "dist_gap": {"value": float(np.max(dist_gap, initial=0.0)),
                     "limit": float(limits["dist_gap"])},
        "id_gap": {"value": float(np.max(id_gap, initial=0.0)),
                   "limit": float(limits["id_gap"])},
        "unanswered": {"value": int(unanswered), "limit": 0},
        "compared": {"value": int(np.size(dist_gap)), "limit": 1},
    }
    correct = (
        checks["dist_gap"]["value"] <= checks["dist_gap"]["limit"]
        and checks["id_gap"]["value"] <= checks["id_gap"]["limit"]
        and checks["unanswered"]["value"] <= 0
        and checks["compared"]["value"] >= 1
    )
    return bool(correct), checks
