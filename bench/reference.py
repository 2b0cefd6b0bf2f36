"""Plain reference: brute-force constrained kNN in float64 on the host.

Independent of the program: numpy only, over the points exactly as the
index holds them (float32 values, widened without rounding). For each
query it returns the ids of the (at most) k points nearest to it among
those within distance r, ascending by distance, padded with id -1 and
distance +inf.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_BLOCK = 32


def _sq_dists(points: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """(m, n) squared distances, difference form, one column at a time."""
    d2 = np.zeros((len(queries), len(points)))
    for j in range(points.shape[1]):
        diff = points[None, :, j] - queries[:, None, j]
        d2 += diff * diff
    return d2


def _block(points, queries, k, r):
    d2 = _sq_dists(points, queries)
    d2[d2 > r * r] = np.inf
    m = len(queries)
    kk = min(k, points.shape[0])
    part = np.argpartition(d2, kk - 1, axis=1)[:, :kk]
    pd = np.take_along_axis(d2, part, axis=1)
    order = np.lexsort((part, pd), axis=1)
    ids = np.take_along_axis(part, order, axis=1).astype(np.int64)
    dd = np.sqrt(np.take_along_axis(pd, order, axis=1))
    ids[~np.isfinite(dd)] = -1
    out_i = np.full((m, k), -1, np.int64)
    out_d = np.full((m, k), np.inf)
    out_i[:, :kk], out_d[:, :kk] = ids, dd
    return out_i, out_d


def constrained_knn(points, queries, k: int, r: float):
    """(ids (m, k) int64, distances (m, k) float64) of the exact answer."""
    points = np.asarray(points, np.float64)
    queries = np.asarray(queries, np.float64).reshape(-1, points.shape[1])
    blocks = [queries[i:i + _BLOCK] for i in range(0, len(queries), _BLOCK)]
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        parts = list(ex.map(lambda q: _block(points, q, k, r), blocks))
    if not parts:
        return np.zeros((0, k), np.int64), np.zeros((0, k))
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]))


def distances_of(points, queries, ids) -> np.ndarray:
    """float64 distance from each query to each id it was answered
    with; +inf where the id is -1 or names no point."""
    points = np.asarray(points, np.float64)
    queries = np.asarray(queries, np.float64).reshape(-1, points.shape[1])
    ids = np.asarray(ids, np.int64)
    ok = (ids >= 0) & (ids < len(points))
    rows = points[np.where(ok, ids, 0)]
    d = np.sqrt(((rows - queries[:, None, :]) ** 2).sum(-1))
    return np.where(ok, d, np.inf)
