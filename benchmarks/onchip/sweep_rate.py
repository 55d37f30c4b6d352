"""Find the highest rate an open-loop cell's server sustains: the knee.

    python benchmarks/onchip/sweep_rate.py --workload <name> \\
        --rates 0.5,0.75,1.0 --seconds <s> --seed <n>

For each rate, in one process and on one set of weights, a fresh server
takes the cell's mix at that rate for one window.  One JSON line per rate
gives the requests due, finished and still waiting for a slot at the
close, the time-to-first-token percentiles, and the waits of the first
and last thirds of the window's arrivals: a rate is sustained when the
queue does not grow through the window.  The cell's rate is then fixed in
its mix at about four fifths of the knee; the benchmark never searches.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
import time

import run_cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = run_cell.load_json(run_cell.ROOT / "BENCHMARK.json")
    cell, c, mix, *_ = run_cell.cell_spec(bench, args.workload)
    run_cell.pin_compile_cache()
    run_cell.chips(cell["chips"])

    import jax
    import e2e_metrics as e2e
    import loadgen
    from client import Client
    from repro.launch import serve
    adapter = importlib.import_module(f"adapters.{c['reference']}")
    params = adapter.program_params(c, run_cell.jax_key(args.seed))
    jax.block_until_ready(params)
    cfg = adapter.model_config(c)
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, arrival=dict(mix["arrival"], rate_per_s=rate))
        reqs = loadgen.generate(m, args.seed, cfg.vocab, args.seconds)
        server = serve.build_server(cfg, params, run_cell.serve_args(
            c["name"], c, m, len(reqs)))
        run_cell.warm_up(server, c)
        t0 = time.perf_counter()
        log = Client(server, reqs, args.seconds).run()
        due = e2e.due_in_window(log)
        waits = [(r.slot_t if r.slot_t is not None else log.t_close) - r.due
                 for r in due]
        third = max(len(waits) // 3, 1)
        tt = e2e.ttfts(log)
        print(json.dumps({
            "rate": rate, "due": len(due),
            "finished": sum(r.tokens is not None for r in due),
            "waiting_at_close": sum(r.slot_t is None for r in due),
            "ttft_p50_s": e2e.percentile(tt, 50),
            "ttft_p90_s": e2e.percentile(tt, 90),
            "tpot_p90_ms": 1e3 * (e2e.percentile(e2e.tpots(log), 90) or 0),
            "wait_first_third_s": sum(waits[:third]) / third,
            "wait_last_third_s": sum(waits[-third:]) / third,
            "tokens_per_s": e2e.tokens_per_s(log),
            "ticks": len(log.ticks), "window_s": log.window_s,
            "wall_s": time.perf_counter() - t0}), flush=True)
        del server
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
