# One function per paper table. Print ``name,us_per_call,derived`` CSV
# and persist each section's datapoints to BENCH_<section>.json (under
# $BENCH_OUT, default ./bench_out) so the perf trajectory survives the
# run — CI uploads these as artifacts.
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--full",
        action="store_true",
        help="paper scale (500k pts, 5300 queries); default is 50k/500",
    )
    ap.add_argument(
        "--only",
        default=None,
        help="comma list: depth,nodes_visited,constrained_nn,search_time,"
        "scalability,kernels,roofline,streaming,serve,faults",
    )
    args = ap.parse_args()

    from repro import compile_cache

    compile_cache.configure()

    from . import (
        constrained_nn,
        depth,
        faults_bench,
        kernels_bench,
        nodes_visited,
        roofline_report,
        scalability,
        search_time,
        serve_bench,
        streaming,
    )

    sections = {
        "depth": depth.run,                      # Fig 5 + Table 1
        "nodes_visited": nodes_visited.run,      # Fig 6
        "constrained_nn": constrained_nn.run,    # Table 2
        "search_time": search_time.run,          # Fig 7a
        "scalability": scalability.run,          # Fig 7b
        "kernels": kernels_bench.run,            # kernel rooflines
        "roofline": roofline_report.run,         # dry-run roofline table
        "streaming": streaming.run,              # LSM mixed read/write
        "serve": serve_bench.run,                # frontend smoke (SLOs)
        "faults": faults_bench.run,              # chaos smoke (failure paths)
    }
    from . import common

    chosen = args.only.split(",") if args.only else list(sections)
    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for name in chosen:
        common.reset_records()
        try:
            sections[name](full=args.full)
        except Exception as e:  # keep the harness running; report failure
            print(f"{name},0.00,ERROR:{type(e).__name__}:{e}", file=sys.stdout)
            failed.append(name)
        else:
            path = common.write_bench_json(name)
            print(f"{name},0.00,json={path}")
    # the registry accumulated across every section above: one artifact
    # holding the counters behind the numbers (dispatches, kernel
    # bytes/FLOPs, index churn), validated by check_bench_schema.py
    obs_path = common.write_obs_json()
    print(f"obs,0.00,json={obs_path}")
    print(f"total,{(time.time() - t0) * 1e6:.0f},bench_wall_time")
    if failed:  # nonzero exit so the CI benchmark-smoke leg catches drift
        sys.exit(f"benchmark sections failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
