"""Readings that a cell's correctness limit is set from.

    python benchmarks/onchip/calibrate.py --workload <name> \\
        --seeds 1,2,3 --seconds <s> [--control]

Runs the cell once per seed in one process (the same code path as
``run_cell.py``, the window included) and prints one JSON line per seed:
the program's verdict and reading of each compared request and, with
``--control``, the float8 control's reading at the same positions and its
verdict under the same checks and limits, which has to be ``false``.  The
limit in ``limits/<cell>.json`` lies above the largest program reading over
a dozen seeds or more and below the smallest control reading (see
PERF.md).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    bench = run_cell.load_json(run_cell.ROOT / "BENCHMARK.json")
    cell, c, mix, limits, e2e, per_layer = run_cell.cell_spec(
        bench, args.workload)
    run_cell.pin_compile_cache()
    devices = run_cell.chips(cell["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell.run(cell, c, mix, limits, e2e, per_layer, seed=seed,
                           seconds=args.seconds, trace=False,
                           devices=devices, t_start=time.perf_counter(),
                           control=args.control)
        reads = out["run"]["readings"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": out["correct"],
            "gap": max(r["gap"] for r in reads),
            "control_gap": (max(r["control_gap"] for r in reads)
                            if args.control else None),
            "control": out.get("control"),
            "metrics": out["metrics"], "run": out["run"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
