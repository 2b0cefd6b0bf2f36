"""The one traffic generator: it reads a mix's parameters and drives a
`SearchFrontend` from client threads.

A mix file (``bench/traffic/<name>.json``) holds:

``loop``                  ``"open"``
``rate_qps``              offered queries per second
``queries``               ``"uniform_bbox"``: uniform in the data's
                          bounding box (the paper's section 5.1)
``k``, ``r_frac``         k nearest within r = r_frac x the bounding
                          box's diagonal
``set_seed``              the seed of the mix's fixed set of work

An open loop sends ``round(rate_qps * seconds)`` requests at the times
of a Poisson process conditioned on that count (sorted uniform draws),
whether or not earlier ones have been answered, and times each from
when it was due. The queries and the gaps between arrivals are one set
drawn from ``set_seed``; a run's seed only orders them (``reorder``),
so that every seed does the same work.
"""
from __future__ import annotations

import time

import numpy as np

LOOPS = ("open",)


def check(traffic: dict) -> dict:
    """Validate a mix's parameters; returns it unchanged."""
    loop = traffic.get("loop")
    if loop not in LOOPS:
        raise ValueError(f"traffic loop must be one of {LOOPS}, got {loop!r}")
    if traffic.get("queries") != "uniform_bbox":
        raise ValueError(f"unknown query distribution {traffic.get('queries')!r}")
    need = ["k", "r_frac", "rate_qps", "set_seed"]
    missing = [key for key in need if key not in traffic]
    if missing:
        raise ValueError(f"traffic lacks {missing}")
    return traffic


def radius(points: np.ndarray, traffic: dict) -> float:
    """r = r_frac x the bounding-box diagonal, as the float32 the index
    compares against."""
    diag = float(np.linalg.norm(points.max(0).astype(np.float64)
                                - points.min(0).astype(np.float64)))
    return float(np.float32(traffic["r_frac"] * diag))


def queries(points: np.ndarray, n: int, rng) -> np.ndarray:
    lo, hi = points.min(0), points.max(0)
    return rng.uniform(lo, hi, size=(n, points.shape[1])).astype(np.float32)


class Window:
    """Per-request record of one measured window: the query row each
    request sent, when it was due and sent, when its future resolved
    (NaN until then), whether with a reply, and the reply's ids and
    distances. Replies are copied into arrays as they resolve and no
    future is kept, so the record adds no object per request to the
    heap that Python's collector scans."""

    def __init__(self, n: int, k: int) -> None:
        self.qrow = np.full(n, -1, np.int64)
        self.due = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.ok = np.zeros(n, bool)
        self.gids = np.full((n, k), -1, np.int64)
        self.dists = np.full((n, k), np.inf, np.float32)
        self.count = 0
        self.t0 = self.t_end = float("nan")

    def _resolved(self, i: int, fut) -> None:
        if fut.exception() is None:
            reply = fut.result()
            self.gids[i] = reply.gids
            self.dists[i] = reply.distances
            self.ok[i] = True
        self.done[i] = time.perf_counter()

    def submit(self, fe, i: int, row: int, q: np.ndarray, due: float):
        self.qrow[i] = row
        self.due[i] = due
        self.sent[i] = time.perf_counter()
        fut = fe.submit(q)
        fut.add_done_callback(lambda f, i=i: self._resolved(i, f))
        return fut

    def wait(self, timeout_s: float) -> None:
        """Wait for every request sent, up to ``timeout_s`` in all."""
        deadline = time.perf_counter() + timeout_s
        while (np.isnan(self.done[: self.count]).any()
               and time.perf_counter() < deadline):
            time.sleep(0.01)

    def answered(self) -> np.ndarray:
        """Mask of requests whose future resolved with a reply."""
        return self.ok[: self.count].copy()


def run(fe, traffic: dict, qpool: np.ndarray, offsets: np.ndarray,
        seconds: float, on_start=None) -> Window:
    """Drive one window of ``seconds`` with the mix's loop: request i
    sends ``qpool[i]`` when ``offsets[i]`` seconds of the window have
    passed. ``on_start`` is called just before the first request is
    due."""
    return _open(fe, traffic, qpool, offsets, seconds, on_start)


def open_schedule(traffic: dict, seconds: float, rng) -> np.ndarray:
    n = int(round(traffic["rate_qps"] * seconds))
    return np.sort(rng.uniform(0.0, seconds, size=n))


def reorder(offsets: np.ndarray, rng) -> np.ndarray:
    """The same arrivals in another order: the gaps between ``offsets``
    (the first offset among them) permuted by ``rng`` and summed again.
    The last arrival stays where it was."""
    gaps = np.diff(np.asarray(offsets, np.float64), prepend=0.0)
    return np.cumsum(rng.permutation(gaps))


def _open(fe, traffic, qpool, offsets, seconds, on_start) -> Window:
    if len(qpool) < len(offsets):
        raise ValueError(f"{len(offsets)} arrivals, {len(qpool)} queries")
    win = Window(len(offsets), int(traffic["k"]))
    if on_start is not None:
        on_start()
    t0 = time.perf_counter()
    win.t0, win.t_end = t0, t0 + seconds
    for i, off in enumerate(offsets):
        due = t0 + off
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        win.submit(fe, i, i, qpool[i], due)
        win.count = i + 1
    left = win.t_end - time.perf_counter()
    if left > 0:
        time.sleep(left)
    return win
