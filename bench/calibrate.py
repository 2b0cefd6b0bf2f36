"""Readings that the correctness limits are set from (run on the chip).

    python3 bench/calibrate.py --workload <cell> --seconds 4 --seeds s1,s2,...

In one process, for each seed: one run of the cell with a short window
at the cell's own load, whose checked replies give the program's
readings (``dist_gap``, ``id_gap``); then the control (``control.py``:
the reference in bfloat16, on the chip, at the cell's full size) on the
same sampled queries, read by the same comparison. One JSON line per
seed on standard output. The lower reading of a number is the largest
the program gives, the upper the smallest the control gives; the limit
in the configuration file lies between them. Not used by the
benchmark's runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    from bench import compare, control, harness

    c = harness.cell(args.workload)
    why = harness.start(c)
    if why is not None:
        print(f"calibrate: {why}", file=sys.stderr)
        return 2
    k = int(c.traffic["k"])
    for seed in (int(x) for x in args.seeds.split(",")):
        out = harness.run_cell(c, seed, args.seconds, False, time.perf_counter())
        queries, points, r = out.sample
        ids, d = control.answers(points, queries, k, r)
        cg = compare.gaps(ids, d, queries, points, k, r)
        c_ok, c_checks = compare.judge(*cg, 0, c.config["limits"])
        print(json.dumps({
            "seed": seed,
            "program": {n: v["value"] for n, v in out.checks.items()},
            "program_correct": out.correct,
            "control": {n: v["value"] for n, v in c_checks.items()},
            "control_correct": c_ok,
            "metrics": {n: v["value"] for n, v in out.metrics.items()},
            "notes": out.notes,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
