"""Chip readings of the expert layer in the qwen3-moe-30b-a3b cell's
configuration (``benchmarks/onchip/configs/qwen3-moe-30b-a3b.json``),
through the benchmark's own set-up (``run_cell``: the adapter's weights
from the seed, ``launch/serve.build_server``, the warm-up request).

* ``counters``: the cell's traffic served for ``--seconds`` by the
  benchmark's client, then ``Scheduler.stats()`` — the expert counters
  (``moe``: units routed to the held experts, dropped, per held expert)
  beside the decode steps and prefill chunks that made them.
* ``layer``: the decode step (every slot active) and the prefill chunk
  (one page of one slot) timed as the scheduler compiled them, and again
  with the expert FFN taken out of both programs (``decode._moe_ffn``
  returns its input).  The difference is the whole expert layer a call —
  router, top-k, compaction, sort, grouped products and combine — of which
  the trace's named instructions (``ragged-dot-*``) are the products only.
* ``routes``: the requests ``--replay`` of the seed's traffic replayed one
  at a time through the program with its routing recorded (each layer's
  top-k as the program computes it, and as a float32 router product from
  the same inputs would), then the plain reference's logits and routing at
  every served position: the gap ``verdict.py`` reads there, and whether
  and where the top-k sets differ.

    python benchmarks/moe_layer_chip.py --seed <n> [--seconds 51] \\
        [--iters 10] [--replay 9,10,16]

Prints one JSON line per part.  Exits 2 without a TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE / "onchip"))
sys.path.insert(1, str(HERE.parent / "src"))

import run_cell  # noqa: E402

CELL = "qwen3-moe-30b-a3b.chat-offline"
HIGHEST = jax.lax.Precision.HIGHEST


def _server(c: dict, mix: dict, params, n_reqs: int = 1):
    from repro.launch import serve
    adapter = run_cell.importlib.import_module(f"adapters.{c['reference']}")
    server = serve.build_server(adapter.model_config(c), params,
                                run_cell.serve_args(c["name"], c, mix,
                                                    n_reqs))
    run_cell.warm_up(server, c)
    return server


def counters(c: dict, mix: dict, params, reqs, seconds: float) -> dict:
    from client import Client
    server = _server(c, mix, params, len(reqs))
    log = Client(server, reqs, seconds).run()
    stats = server.scheduler.stats()
    del server
    gc.collect()
    return {"part": "counters", "moe": stats["moe"], "ticks": len(log.ticks),
            "decode_steps": stats["decode_steps"],
            "prefill_chunks": stats["prefill_chunks"],
            "window_s": log.window_s}


def _ms(fn, iters: int) -> float:
    jax.block_until_ready(fn())
    walls = []
    for _ in range(iters):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        walls.append(time.perf_counter() - t)
    return 1e3 * statistics.median(walls)


def _program_ms(c: dict, mix: dict, params, iters: int) -> dict:
    """Median ms a call of the decode step over every slot and of one
    page-wide prefill chunk, on the server's fresh state."""
    sched = _server(c, mix, params).scheduler
    ps = sched.cache.page_size
    tok = jnp.ones((sched.slots,), jnp.int32)
    act = jnp.ones((sched.slots,), bool)
    chunk = jnp.arange(1, ps + 1, dtype=jnp.int32)

    def step():
        logits, sched.cache.state = sched._step(params, sched.cache.state,
                                                tok, act)
        return logits

    def prefill():
        sched.cache.state = sched._chunk(params, sched.cache.state, chunk,
                                         jnp.int32(0), jnp.int32(ps))
        return sched.cache.state
    out = {"decode_step": _ms(step, iters),
           "prefill_chunk": _ms(prefill, iters)}
    del sched
    gc.collect()
    return out


@contextlib.contextmanager
def _patched(obj, name: str, value):
    real = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield real
    finally:
        setattr(obj, name, real)


def layer(c: dict, mix: dict, params, iters: int,
          counted: dict | None = None) -> dict:
    from repro.models import decode
    whole = _program_ms(c, mix, params, iters)

    def no_expert(p, x, cfg, valid):
        zero = jnp.zeros((), jnp.int32)
        return x, {"routed": zero, "dropped": zero,
                   "per_expert": jnp.zeros((cfg.moe.n_held,), jnp.int32)}
    with _patched(decode, "_moe_ffn", no_expert):
        bare = _program_ms(c, mix, params, iters)
    out = {"part": "layer", "with_ms": whole, "without_ms": bare}
    out["layer_share"] = {k: 1 - bare[k] / whole[k] for k in whole}
    if counted:
        # the two programs' device time in the counted window, and the
        # expert layer's part of it, from the calls the window made
        n = {"decode_step": counted["decode_steps"],
             "prefill_chunk": counted["prefill_chunks"]}
        total = sum(n[k] * whole[k] for k in n)
        out["window_layer_share"] = sum(
            n[k] * (whole[k] - bare[k]) for k in n) / total
    return out


def _top(logits, k: int):
    """Top-k ids and the logit margin between the k-th and the next."""
    v, i = jax.lax.top_k(logits, k + 1)
    return i[:, :k], v[:, k - 1] - v[:, k]


def _program_routes(c: dict, mix: dict, params, reqs) -> list[dict]:
    """Each request served alone; per layer, in the order the program
    processed them, the valid rows' top-k (program and float32 router
    product) and margin."""
    from repro.models import decode, layers
    rows: list = []
    on = [False]

    def sink(tag, topi, margin, top32, valid):
        if on[0]:
            v = np.asarray(valid)
            rows.append((float(tag), np.asarray(topi)[v],
                         np.asarray(margin)[v], np.asarray(top32)[v]))

    def recorded(real):
        def rec(p, x, cfg, valid):
            h = layers.rms_norm(x, p["ln2"], cfg.norm_eps).reshape(
                -1, cfg.d_model)
            r = p["moe"]["router"]
            # as moe_ffn_local computes it
            topi, margin = _top((h @ r.astype(h.dtype)).astype(jnp.float32),
                                cfg.moe.top_k)
            top32, _ = _top(jnp.matmul(h.astype(jnp.float32), r,
                                       precision=HIGHEST), cfg.moe.top_k)
            jax.debug.callback(sink, r[0, 0], topi, margin, top32,
                               valid.reshape(-1), ordered=True)
            return real(p, x, cfg, valid)
        return rec
    tags = np.asarray(params["blocks"]["pos0"]["moe"]["router"][:, 0, 0],
                      np.float32).tolist()
    out = []
    with _patched(decode, "_moe_ffn", recorded(decode._moe_ffn)):
        server = _server(c, mix, params)
        for r in reqs:
            rows.clear()
            on[0] = True
            req = server.submit(r.prompt.tolist(), max_new_tokens=r.max_new)
            while not server.scheduler.drained():
                server.tick()
            jax.effects_barrier()
            on[0] = False
            per_layer = [[] for _ in tags]
            for tag, topi, margin, top32 in rows:
                per_layer[tags.index(tag)].append((topi, margin, top32))
            out.append({"idx": r.idx, "prompt": r.prompt,
                        "tokens": list(req.tokens[len(r.prompt):]),
                        "layers": [
                            tuple(np.concatenate(x) for x in zip(*lay))
                            for lay in per_layer]})
        del server
    jax.clear_caches()
    gc.collect()
    return out


def _reference_routes(c: dict, key, served: list) -> list[dict]:
    """Per served request: the reference's gaps at the served positions
    and, per layer, its top-k and margin there."""
    import verdict
    from references import dense_decoder as dense
    from references import moe_decoder as ref
    rows: list = []

    def sink(tag, topi, margin):
        rows.append((np.asarray(topi), np.asarray(margin)))

    real = ref._experts

    def rec(m, quant, x, w):
        h = dense._rms(x, w["ln2"], m["eps"])
        topi, margin = _top(dense._mm(h, w["router"], quant), m["k"])
        jax.debug.callback(sink, w["router"][0, 0], topi, margin,
                           ordered=True)
        return real(m, quant, x, w)
    weights = ref.init(c, key)
    S = c["serving"]["max_len"]
    out = []
    with _patched(ref, "_experts", rec):
        for s in served:
            P, n = len(s["prompt"]), len(s["tokens"])
            seq = np.zeros(S, np.int32)
            seq[:P + n - 1] = np.concatenate([s["prompt"], s["tokens"][:-1]])
            pos = np.arange(P - 1, P - 1 + n, dtype=np.int32)
            rows.clear()
            lg = ref.logits(c, weights, jnp.asarray(seq), jnp.asarray(pos))
            gaps = np.asarray(verdict._gaps(lg, jnp.asarray(s["tokens"])))
            jax.effects_barrier()
            out.append({"gaps": gaps, "layers": [
                (t[P - 1:P - 1 + n], g[P - 1:P - 1 + n]) for t, g in rows]})
    jax.clear_caches()      # no later trace keeps the recording layer
    del weights
    gc.collect()
    return out


def routes(c: dict, mix: dict, key, params_fn, reqs) -> list[dict]:
    """One line per replayed request: its widest gap and where it lies,
    and the served positions whose top-k sets differ from the
    reference's in some layer (``flips``), by the program's router and by
    a float32 router product; a flip is ``held`` when an expert of the
    held range enters or leaves, the only flips that change the output
    beyond the renormalisation."""
    params = params_fn()
    served = _program_routes(c, mix, params, reqs)
    del params
    gc.collect()
    refs = _reference_routes(c, key, served)
    lo, held = c["first_expert"], c["num_experts"]
    lines = []
    for s, r in zip(served, refs):
        n, P = len(s["tokens"]), len(s["prompt"])
        assert all(len(t) == P - 1 + n for t, _, _ in s["layers"])
        prog = [(t[P - 1:P - 1 + n], m[P - 1:P - 1 + n], t32[P - 1:P - 1 + n])
                for t, m, t32 in s["layers"]]

        def flips(col):
            """(positions, layers) where the sets differ; and held ones."""
            any_, held_ = np.zeros((n, len(prog)), bool), np.zeros(
                (n, len(prog)), bool)
            for li, (p, (rt, _)) in enumerate(zip(prog, r["layers"])):
                for j in range(n):
                    a, b = set(p[col][j].tolist()), set(rt[j].tolist())
                    any_[j, li] = a != b
                    held_[j, li] = any(lo <= e < lo + held for e in a ^ b)
            return any_, held_
        f_any, f_held = flips(0)
        f32_any, f32_held = flips(2)
        g = r["gaps"]
        w = int(np.argmax(g))
        agree = ~f_held.any(1)
        lines.append({
            "part": "routes", "idx": s["idx"], "prompt": P, "served": n,
            "gap_max": float(g[w]), "widest_at": w,
            "widest_flip_layers": np.flatnonzero(f_any[w]).tolist(),
            "widest_held_flip_layers": np.flatnonzero(f_held[w]).tolist(),
            "widest_ref_margin": [float(r["layers"][li][1][w])
                                  for li in np.flatnonzero(f_any[w])],
            "widest_prog_margin": [float(prog[li][1][w])
                                   for li in np.flatnonzero(f_any[w])],
            "positions_flipped": int(f_any.any(1).sum()),
            "positions_held_flipped": int(f_held.any(1).sum()),
            "positions_flipped_f32": int(f32_any.any(1).sum()),
            "positions_held_flipped_f32": int(f32_held.any(1).sum()),
            "layer_flips": int(f_any.sum()),
            "layer_flips_f32": int(f32_any.sum()),
            "positions_gap_nonzero": int((g > 0).sum()),
            "gap_max_held_agree": float(np.max(g[agree], initial=0.0)),
            "gap_max_held_flipped": float(np.max(g[~agree], initial=0.0))})
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--replay", default="")
    args = ap.parse_args(argv)
    bench = run_cell.load_json(run_cell.ROOT / "BENCHMARK.json")
    _, c, mix, *_ = run_cell.cell_spec(bench, CELL)
    run_cell.pin_compile_cache()
    try:
        run_cell.chips(1)
    except run_cell.NoChip as e:
        print(f"moe_layer_chip: {e}", file=sys.stderr)
        return run_cell.EXIT_NO_CHIP
    return run(c, mix, args.seed, args.seconds, args.iters,
               [int(i) for i in args.replay.split(",") if i])


def run(c: dict, mix: dict, seed: int, seconds: float, iters: int,
        replay: list) -> int:
    import loadgen
    adapter = run_cell.importlib.import_module(f"adapters.{c['reference']}")
    key = run_cell.jax_key(seed)
    reqs = loadgen.generate(mix, seed, adapter.model_config(c).vocab,
                            seconds)
    params = adapter.program_params(c, key)
    counted = counters(c, mix, params, reqs, seconds)
    print(json.dumps(counted), flush=True)
    print(json.dumps(layer(c, mix, params, iters, counted)), flush=True)
    del params
    gc.collect()
    if replay:
        for line in routes(c, mix, key,
                           lambda: adapter.program_params(c, key),
                           [reqs[i] for i in replay]):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
