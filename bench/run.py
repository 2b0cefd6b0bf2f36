"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are read from
``BENCHMARK.json`` and the files it names under ``bench/``. The run
exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program cannot be imported.
Otherwise the last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` too, with ``--trace 1``) and last ``checks``: each number
the correctness check compared, beside its limit. The same checks are
the last lines of standard error.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are
the cell's per-layer metrics.

``--sweep RATES`` (comma-separated queries/s)
is a one-time tool to find a cell's knee: it runs one window per rate
on one set-up and prints a table; it prints no result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None)
    args = ap.parse_args(argv)

    from bench import harness

    if args.sweep:
        from bench import sweep

        rates = [float(x) for x in args.sweep.split(",")]
        return sweep.run(args.workload, args.seed, args.seconds, rates)
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)


if __name__ == "__main__":
    sys.exit(main())
