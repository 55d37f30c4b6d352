"""Bring-up smoke test: serve qwen3-0.6b at its published widths on one TPU.

Run from the repository root of a checkout, on a host with one TPU chip:

    python chip_smoke.py

It drives the serving main path through the code ``python -m
repro.launch.serve`` uses (``repro.launch.serve``: model loading, server
construction, seeded prompts, the tick loop and the finish audit):

1. Device: JAX must see a TPU, Pallas kernels must compile for it (not run
   in the interpreter), the default vector-access lowering must be
   ``pallas``, and the compiled decode step must contain a Mosaic kernel
   (``tpu_custom_call``).
2. Serve: 8 seeded requests, prompts of 64..512 tokens, 32 new tokens
   each, greedy, through ``Scheduler`` (slots=8, max_len=2048,
   page_size=16, float32 page pool) until it drains.  Every request must
   end FINISHED with exactly 32 new tokens.
3. Reference: two of the requests served again, prefill and decode,
   under ``vx.use("ref")`` (the XLA lowering) on a fresh 2-slot
   scheduler must reproduce phase 2's greedy token streams and
   first-decode logits bit for bit.

A failed phase exits nonzero.  On success the last line of stdout is one
JSON object naming the device.  A bring-up smoke, not a benchmark: the
walls it prints include compilation and host work.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import vx  # noqa: E402
from repro.kernels import _common  # noqa: E402
from repro.launch import compile_cache, serve  # noqa: E402

ARCH = "qwen3-0.6b"
SLOTS = 8
MAX_LEN = 2048
PAGE_SIZE = 16
GEN = 32
PROMPT_LENS = (64, 128, 192, 256, 320, 384, 448, 512)
REPLAY = (0, 7)          # shortest and longest prompt


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def serve_args(slots: int):
    return serve.parse_args([
        "--arch", ARCH, "--requests", str(slots), "--gen", str(GEN),
        "--max-len", str(MAX_LEN), "--page-size", str(PAGE_SIZE),
        "--prompt-len", str(max(PROMPT_LENS))])


def phase_device():
    print(f"jax {jax.__version__}; devices: {jax.devices()}", flush=True)
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"default device is {dev.platform!r}, not a TPU")
    check(not _common.interpret_mode(),
          "Pallas kernels would run in interpret mode")
    impl = vx.Policy.default().impl
    check(impl == "pallas", f"default vx lowering is {impl!r}, not pallas")
    return dev


def build(slots: int):
    """(args, cfg, params, server) as ``repro.launch.serve`` builds them."""
    args = serve_args(slots)
    t0 = time.perf_counter()
    cfg, params = serve.load_model(args)
    server = serve.build_server(cfg, params, args)
    print(f"model {cfg.name}: {cfg.n_layers} layers d{cfg.d_model} "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads} hd{cfg.hd} "
          f"vocab {cfg.vocab}; pool {server.scheduler.cache.num_pages} "
          f"pages of {args.page_size}; built in "
          f"{time.perf_counter() - t0:.3f}s", flush=True)
    return args, cfg, params, server


def phase_kernel(sched) -> None:
    t0 = time.perf_counter()
    kernels = sched.compile_decode().as_text().count("tpu_custom_call")
    print(f"decode step compiled in {time.perf_counter() - t0:.3f}s; "
          f"tpu_custom_call x{kernels}", flush=True)
    check(kernels > 0, "the compiled decode step has no tpu_custom_call")


def serve_requests(server, args, prompts):
    """Submit ``prompts`` (``GEN`` new tokens each) and tick until the
    scheduler drains.  Returns the requests, each one's logits row from the
    step that made its first token, the tick count, the first tick's and
    the whole run's walls, and the peak of pages in use."""
    reqs = [server.submit(p, max_new_tokens=GEN) for p in prompts]
    logits: dict = {}
    peak_pages = [0]

    def on_tick(s):
        for r in reqs:
            if (r.rid not in logits and not r.terminal and r.slot is not None
                    and len(s.tokens[r.slot]) - len(r.prompt) == 1):
                logits[r.rid] = np.asarray(s.last_logits[r.slot], np.float32)
        peak_pages[0] = max(peak_pages[0], s.cache.pages_in_use())

    ticks, first, total = serve.serve_until_drained(
        server, serve.tick_cap(args), on_tick)
    check(server.scheduler.drained(), f"tick cap hit after {ticks} ticks")
    problems = serve.unfinished(reqs, GEN)
    check(not problems, "; ".join(problems))
    check(len(logits) == len(reqs), "missed a first-decode logits row")
    return reqs, [logits[r.rid] for r in reqs], ticks, first, total, \
        peak_pages[0]


def phase_serve(args, cfg, server, dev):
    """Serve the 8 requests; returns the prompts, token streams and
    first-decode logits rows of the ``REPLAY`` requests."""
    sched = server.scheduler
    prompts = serve.make_prompts(cfg.vocab, PROMPT_LENS)
    reqs, rows, ticks, first, total, peak = serve_requests(
        server, args, prompts)
    for r in reqs:
        print(f"req {r.rid}: {r.state.value} prompt={len(r.prompt)} "
              f"new={r.generated} {r.tokens[len(r.prompt):][:8]} ...")
    print(f"first tick (compiles the prefill-chunk program): {first:.3f}s",
          flush=True)
    print(f"warm wall: {total - first:.3f}s over {ticks - 1} ticks")
    print(f"tokens generated: {sum(r.generated for r in reqs)}")
    print(f"pages in use: peak {peak} of {sched.cache.num_pages}, "
          f"{sched.cache.pages_in_use()} after drain")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    return ([prompts[i] for i in REPLAY],
            [list(reqs[i].tokens) for i in REPLAY], [rows[i] for i in REPLAY])


def _bits(a) -> np.ndarray:
    a = np.asarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


def phase_reference(cfg, params, prompts, streams, rows) -> None:
    """Serve the ``REPLAY`` requests again, prefill and decode, under
    ``vx.use("ref")`` (the XLA lowering) on a fresh 2-slot scheduler.  The
    kernels only move data, so the token streams and first-decode logits
    must equal phase 2's bit for bit."""
    live = sum(a.nbytes for a in jax.live_arrays())
    print(f"live device bytes before the reference run: {live}")
    args = serve_args(len(prompts))
    with vx.use("ref"):
        server = serve.build_server(cfg, params, args)
        reqs, got_rows, ticks, _, total, _ = serve_requests(
            server, args, prompts)
    print(f"reference (XLA lowering, {len(prompts)} slots): {ticks} ticks "
          f"in {total:.3f}s")
    ok = True
    for r, want, row, got_row in zip(reqs, streams, rows, got_rows):
        got = list(r.tokens)
        same = got == want
        exact = bool(np.array_equal(_bits(got_row), _bits(row)))
        print(f"prompt {len(r.prompt)}: streams "
              f"{'identical' if same else 'DIFFER'}; first-decode logits "
              f"bit-identical={exact}, max |gap| "
              f"{float(np.max(np.abs(got_row - row)))!r}")
        if not same:
            at = next(i for i, (x, y) in enumerate(zip(got, want)) if x != y)
            print(f"  first divergence at new token {at - len(r.prompt)}: "
                  f"pallas {want[at:at + 4]} vs ref {got[at:at + 4]}")
        ok = ok and same and exact
    check(ok, "the Pallas and XLA lowerings disagree")


def main() -> int:
    try:
        dev = phase_device()
        cache = compile_cache.enable()
        warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        print(f"compile cache: {cache} ({warm} entries at start)")
        args, cfg, params, server = build(SLOTS)
        phase_kernel(server.scheduler)
        replay = phase_serve(args, cfg, server, dev)
        del server
        gc.collect()           # two full page pools do not fit together
        phase_reference(cfg, params, *replay)
    except SmokeFailure as e:
        print(f"FAIL: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
