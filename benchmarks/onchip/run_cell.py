"""Run one benchmark cell once on the chip and print its result line.

    python benchmarks/onchip/run_cell.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell (``BENCHMARK.json``'s
``workloads``) names a configuration (``configs/<config>.json``) and a
traffic mix (``traffic/<mix>.json``); its correctness limit is in
``limits/<cell>.json`` and each per-layer metric is read by
``metrics/<metric>.py``.  Adding a cell, a configuration, a mix or a
metric adds files and entries and edits nothing here.

Set-up (timed as ``setup_s``, from process start to window open): weights
made on the device by one jitted program from the seed, the server built
by ``repro.launch.serve.build_server`` (the serve CLI's path), one warm-up
request through the same scheduler so that every program the window runs
is compiled, and the cell's traffic drawn from the seed.  The compile
cache is pinned to ``<checkout>/.jax_cache``.

The window then drives ``Scheduler.tick`` for ``--seconds`` (``client``).
With ``--trace 1`` the window runs under the profiler and the line carries
the cell's per-layer metrics and a breakdown; otherwise its end-to-end
metrics.  After the window the device's peak memory is read (``memory``),
the server is freed, and the served tokens are compared with the reference
(``verdict``).  The last line of stdout is the result; the numbers
compared, each beside its limit, are the last lines of stderr.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

EXIT_NO_CHIP = 2


class NoChip(RuntimeError):
    pass


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(bench: dict, name: str):
    """(cell, configuration, mix, limits, end-to-end metric names,
    {per-layer metric name: unit})."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    c = load_json(ROOT / conf["file"])
    mix = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(HERE / "limits" / f"{name}.json")

    def mine(m):
        return name in m.get("workloads", [name])
    return (cell, c, mix, limits,
            [m["name"] for m in bench["end_to_end"] if mine(m)],
            {m["name"]: m["unit"] for m in bench["per_layer"] if mine(m)})


def pin_compile_cache() -> None:
    """Every run of a checkout shares ``<checkout>/.jax_cache``, whatever
    the environment says, and caches every program however fast it
    compiled."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(n: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (default device: "
                     f"{devs[0].platform} {devs[0].device_kind})")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX finds {len(devs)}")
    return devs[:n]


def jax_key(seed: int):
    """The weights' key: any whole number, 64-bit seeds included."""
    import jax
    import numpy as np
    return jax.random.key(
        int(np.random.SeedSequence(seed).generate_state(1)[0]))


def serve_args(cfg_name: str, c: dict, mix: dict, n_reqs: int):
    from repro.launch import serve
    s = c["serving"]
    return serve.parse_args([
        "--arch", cfg_name, "--requests", str(s["slots"]),
        "--max-len", str(s["max_len"]), "--page-size", str(s["page_size"]),
        "--kv-dtype", s["kv_dtype"], "--chunk-pages", str(s["chunk_pages"]),
        "--queue-depth", str(n_reqs + s["slots"])])


def warm_up(server, c: dict) -> None:
    """One request of two prefill chunks and two new tokens: compiles the
    prefill chunk, the decode step, sampling and slot release, the only
    programs the window runs."""
    ps = c["serving"]["page_size"]
    server.submit(list(range(1, ps + 3)), max_new_tokens=2)
    while not server.scheduler.drained():
        server.tick()
    server.scheduler.cache.pages_in_use()


def memory(server, c: dict, dev) -> dict:
    """The device's peak memory over the window, and its parts.

    ``peak_bytes_in_use`` counts the buffers the runtime hands out but not
    the temporaries a program allocates while it runs; on a TPU those are
    most of the decode step's memory (its gathered page rows and their
    split).  So the peak is also reckoned from XLA's own memory analysis of
    the window's two large programs, the decode step and the prefill chunk,
    compiled again as the scheduler calls them (from the compile cache):
    the resident bytes (weights and page pool) plus the most either needs
    beyond its arguments (temporaries and outputs not aliased to an
    argument).  ``memory_peak_bytes`` is the larger of the two readings."""
    import jax
    import jax.numpy as jnp
    sched = server.scheduler
    t0 = time.perf_counter()
    resident = sum(a.nbytes for a in jax.tree.leaves(
        (sched.params, sched.cache.state)))
    tok = jnp.zeros((c["serving"]["page_size"],), jnp.int32)
    programs = {
        "decode_step": sched.compile_decode(),
        "prefill_chunk": sched._chunk.lower(
            sched.params, sched.cache.state, tok, jnp.int32(0),
            jnp.int32(1)).compile()}
    extra = {}
    for name, compiled in programs.items():
        m = compiled.memory_analysis()
        extra[name] = {"temp": m.temp_size_in_bytes,
                       "unaliased_out": (m.output_size_in_bytes
                                         - m.alias_size_in_bytes)}
    need = resident + max(sum(v.values()) for v in extra.values())
    in_use = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    return {"memory_peak_bytes": max(in_use or 0, need),
            "peak_bytes_in_use": in_use, "resident_bytes": resident,
            "program_bytes": extra,
            "analysis_s": time.perf_counter() - t0}


@dataclasses.dataclass
class View:
    """What a per-layer metric's reader may read."""
    cell: str
    config: dict
    mix: dict
    log: object            # client.Log
    trace: object          # trace_reduce.Summary, or None
    peaks: dict


def read_metric(name: str, view: View):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"),
        HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(view)


def run(cell: dict, c: dict, mix: dict, limits: dict, e2e: list,
        per_layer: dict, *, seed: int, seconds: float, trace: bool,
        devices: list, t_start: float = T_START,
        control: bool = False) -> dict:
    """One run of a cell on ``devices``; returns the result object.
    ``control`` adds the float8 control's reading of each compared request
    and its verdict (``calibrate.py``; the benchmark's own runs never read
    it)."""
    import jax
    import loadgen
    import peaks as peak_table
    import trace_reduce
    import verdict
    from client import Client
    from e2e_metrics import due_in_window, end_to_end
    from repro.launch import serve

    dev = devices[0]
    adapter = importlib.import_module(f"adapters.{c['reference']}")
    key = jax_key(seed)
    params = adapter.program_params(c, key)
    jax.block_until_ready(params)
    cfg = adapter.model_config(c)
    reqs = loadgen.generate(mix, seed, cfg.vocab, seconds)
    server = serve.build_server(cfg, params, serve_args(c["name"], c, mix,
                                                        len(reqs)))
    del params
    warm_up(server, c)
    tdir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        # device and host tracing, without the Python tracer (which
        # records every Python call and slows the host it measures)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir.name, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    log = Client(server, reqs, seconds, spans=trace, pool=trace).run()
    if trace:
        jax.profiler.stop_trace()
    mem = memory(server, c, dev)
    preemptions = server.scheduler.preemptions
    del server
    gc.collect()

    judged = verdict.judge(log, c, key, mix, limits, seed, control=control)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": mem["memory_peak_bytes"]}
    due = due_in_window(log)
    out = {"correct": judged["correct"], "attempted": len(due),
           "failed": judged["failed"]}
    if trace:
        summary = trace_reduce.load(tdir.name)
        tdir.cleanup()
        view = View(cell["name"], c, mix, log, summary,
                    peak_table.for_kind(dev.device_kind))
        metrics = {}
        for name, unit in per_layer.items():
            v = read_metric(name, view)
            if v is not None:
                metrics[name] = {"value": v, "unit": unit}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out.update(metrics=metrics, device=device,
                   breakdown=trace_reduce.breakdown(summary))
    else:
        vals = end_to_end(log, setup_s)
        out.update(metrics={n: {"value": vals[n][0], "unit": vals[n][1]}
                            for n in e2e if n in vals}, device=device)
    out["run"] = {"window_s": log.window_s, "ticks": len(log.ticks),
                  "compiles_in_window": log.compiles_in_window,
                  "preemptions": preemptions, "setup_s": setup_s,
                  "finished": sum(r.tokens is not None for r in due),
                  "memory": mem,
                  "readings": judged["readings"]}
    if control:
        out["control"] = judged["control"]
    out["checks"] = judged["checks"]
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell, c, mix, limits, e2e, per_layer = cell_spec(bench, args.workload)
    pin_compile_cache()
    try:
        devices = chips(cell["chips"])
    except NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    out = run(cell, c, mix, limits, e2e, per_layer, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace), devices=devices)
    print(json.dumps(out), flush=True)
    for name, chk in out["checks"].items():
        print(f"check {name}: {chk['value']} (limit {chk['limit']})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
