"""Benchmark harness — one suite per paper table/figure.

Prints ``name,us_per_call,derived`` CSV, writes machine-readable
``BENCH_strided.json`` / ``BENCH_segment.json`` artifacts (name,
us_per_call, coalescing factor, compiled-vs-dynamic ratios) so the perf
trajectory is tracked across PRs, and appends the roofline table (from
dry-run artifacts, if present).

  Fig. 11 -> bench_diverse      Fig. 12 -> bench_strided
  Fig. 13 -> bench_segment      Table 2 / Fig. 14/15 -> bench_hw_cost
  (framework) MoE dispatch -> bench_moe

``--quick`` runs a reduced sweep (the CI smoke: < 60 s on a laptop core).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

BENCH_JSON = {
    "strided/": "BENCH_strided.json",
    "segment/": "BENCH_segment.json",
    "moe/": "BENCH_moe.json",
    "step/": "BENCH_step.json",
    "serve/": "BENCH_serve.json",
}


def _write_artifacts(records, out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    for prefix, fname in BENCH_JSON.items():
        rows = [r for r in records if r["name"].startswith(prefix)]
        if not rows:
            continue
        path = os.path.join(out_dir, fname)
        with open(path, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"# wrote {path} ({len(rows)} records)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweep (CI smoke)")
    ap.add_argument("--out", default=os.path.dirname(os.path.abspath(
        __file__)), help="directory for BENCH_*.json artifacts")
    ap.add_argument("--suites", default="all",
                    help="comma list: diverse,strided,segment,hw_cost,"
                         "moe,step,serve")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()

    from benchmarks import common
    common.QUICK = args.quick

    from benchmarks import (bench_diverse, bench_hw_cost, bench_moe,
                            bench_segment, bench_serve, bench_step,
                            bench_strided, roofline_table)
    suites = {
        "diverse": bench_diverse, "strided": bench_strided,
        "segment": bench_segment, "hw_cost": bench_hw_cost,
        "moe": bench_moe, "step": bench_step, "serve": bench_serve,
    }
    if args.suites == "all":
        # the whole registry; --quick reduces each suite's sweep via
        # common.QUICK rather than dropping suites, so the CI smoke
        # exercises every dispatch path end to end
        picked = list(suites)
    else:
        picked = [s.strip() for s in args.suites.split(",")]
    unknown = [s for s in picked if s not in suites]
    if unknown:
        ap.error(f"unknown suites {unknown}; choose from {sorted(suites)}")

    print("name,us_per_call,derived")
    for name in picked:
        suites[name].run()
    _write_artifacts(common.RECORDS, args.out)
    if not args.quick:
        print()
        print("# Roofline table (from experiments/artifacts, if populated):")
        try:
            roofline_table.run()
        except Exception as e:  # noqa: BLE001
            print(f"# (no artifacts: {e})")


if __name__ == "__main__":
    main()
