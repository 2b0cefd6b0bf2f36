"""Find an open-loop cell's knee on the chip: one set-up, then one
window per offered rate, each followed by a drain. For each rate it
prints the answered rate inside the window, the latency quantiles from
the due time, the median latency of the window's first and last thirds
and the requests still unanswered at the window's close. The queue
grows where the last third waits clearly longer than the first and the
backlog at the close exceeds a couple of batches. Not used by the
benchmark's runs; the rate chosen goes into the cell's traffic file.
"""
from __future__ import annotations

import json
import sys

import numpy as np

from . import harness, load


def run(workload: str, seed: int, seconds: float, rates) -> int:
    c = harness.cell(workload)
    why = harness.start(c)
    if why is not None:
        print(f"sweep: {why}", file=sys.stderr)
        return 2
    print("sweep rates_qps " + ",".join(map(str, rates)), file=sys.stderr)
    s = harness.serve(c, seed)
    counter = harness.CompileCounter.get()
    print("set-up phases (s, compiles): " + ", ".join(
        f"{k} {v[0]:.3f} {v[1]}" for k, v in s.setup_parts.items()), file=sys.stderr)
    print(f"warm queries by frontier width class: {s.width_classes}",
          file=sys.stderr, flush=True)
    try:
        for i, rate in enumerate(rates):
            traffic = dict(c.traffic, rate_qps=rate)
            g = harness.rng(seed, 100 + i)
            offsets = load.open_schedule(traffic, seconds, g)
            qpool = load.queries(s.points, len(offsets), g)
            c0 = counter.n
            win = load.run(s.fe, traffic, qpool, offsets, seconds)
            compiles = counter.n - c0
            win.wait(harness.WAIT_AFTER_CLOSE_S)
            print(json.dumps(dict(row(rate, win, seconds), compiles=compiles)),
                  flush=True)
    finally:
        s.fe.stop()
    return 0


def row(rate: float, win, seconds: float) -> dict:
    n = win.count
    ok = win.answered()
    due, done = win.due[:n], win.done[:n]
    lat = (done - due) * 1e3
    third = (due - win.t0) / seconds
    first, last = ok & (third < 1 / 3), ok & (third >= 2 / 3)
    return {
        "rate_qps": rate,
        "sent": n,
        "answered": int(ok.sum()),
        "answered_in_window_qps": float(np.sum(ok & (done <= win.t_end))) / seconds,
        "p50_ms": float(np.percentile(lat[ok], 50)) if ok.any() else None,
        "p95_ms": float(np.percentile(lat[ok], 95)) if ok.any() else None,
        "p99_ms": float(np.percentile(lat[ok], 99)) if ok.any() else None,
        "p50_first_third_ms": float(np.median(lat[first])) if first.any() else None,
        "p50_last_third_ms": float(np.median(lat[last])) if last.any() else None,
        "backlog_at_close": int(np.sum(~(done <= win.t_end))),
        "lateness_p95_ms": float(np.percentile((win.sent[:n] - due) * 1e3, 95)),
    }
