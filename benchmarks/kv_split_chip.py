"""Chip microbenchmark of the FIELD=2 KV split (the segment load).

Times the two routes of ``kernels/segment.py`` on one TPU at the served
qwen3-0.6b shapes, float32:

* ``pool``: the decode step's whole-step split, every layer's gathered
  rows at once, (28 * 8 * 2048 * 8, 256) -> 2 x (3670016, 128);
* ``row``: the prefill chunk's per-layer split of one slot's row,
  (2048 * 8, 256), 28 launches in one program (one per layer).

Routes: ``shift`` (the cost-modeled shift plans, the route before the
transpose route) and ``transpose`` at each block height that fits.  The
input is random 32-bit words viewed as float32 (NaNs, infinities and -0.0
among them); every output is compared word for word with ``x[:, f::2]``,
and at the pool shape the two routes with each other.

    python benchmarks/kv_split_chip.py [--iters 10]

Prints one JSON line per (shape, route, block height).  Exits 2 without a
TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.kernels import segment  # noqa: E402

HBM_BYTES_PER_S = 819e9          # TPU v5e (Google Cloud, "TPU v5e")
SHAPES = {"pool": (1, 3670016), "row": (28, 16384)}   # (launches, rows)
N, FIELDS = 256, 2
HEIGHTS = (128, 256, 512, 1024, 2048)


def _split(x, rt: int):
    """The shift plans (``rt`` 0) or the transpose route in ``rt``-row
    blocks."""
    if rt:
        return segment._deint_transpose(x, FIELDS, rt)
    return segment._deint_shift(x, FIELDS, True)


def _mismatches(x, outs):
    """Words of each output that differ from the strided slice."""
    bits = jax.lax.bitcast_convert_type
    return sum(jnp.sum(bits(o, jnp.uint32) != bits(x[:, f::FIELDS],
                                                   jnp.uint32))
               for f, o in enumerate(outs))


def _time(fn, xs, iters):
    jax.block_until_ready(fn(xs))                     # compile, warm
    walls = []
    for _ in range(iters):
        t = time.perf_counter()
        jax.block_until_ready(fn(xs))
        walls.append(time.perf_counter() - t)
    return walls


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    for shape, (launches, rows) in SHAPES.items():
        words = jax.jit(lambda k, rows=rows: jax.lax.bitcast_convert_type(
            jax.random.bits(k, (rows, N), jnp.uint32), jnp.float32))
        xs = [words(k) for k in jax.random.split(jax.random.key(14),
                                                  launches)]
        routes = [("shift", 0)] + [
            ("transpose", h) for h in HEIGHTS
            if rows % h == 0 and 5 * h * N * 4 <= segment._VMEM_BYTES]
        for route, rt in routes:
            fn = jax.jit(lambda xs, rt=rt: [_split(x, rt) for x in xs])
            walls = _time(fn, xs, args.iters)
            outs = fn(xs)
            bad = int(sum(jax.jit(_mismatches)(x, o)
                          for x, o in zip(xs, outs)))
            del outs
            per_launch = statistics.median(walls) / launches
            moved = 2 * rows * N * 4                   # read once, write once
            line = {"shape": shape, "rows": rows, "n": N, "route": route,
                    "block_rows": rt or None, "launches": launches,
                    "ms": per_launch * 1e3,
                    "ms_min": min(walls) / launches * 1e3,
                    "gb_per_s": moved / per_launch / 1e9,
                    "hbm_share": moved / per_launch / HBM_BYTES_PER_S,
                    "mismatched_words": bad,
                    "chosen": route == "transpose" and rt ==
                    segment.transpose_block_rows(rows, N, FIELDS,
                                                 jnp.float32),
                    "device": dev.device_kind}
            print(json.dumps(line), flush=True)
        if shape == "pool":
            x = xs[0]
            a = jax.jit(lambda x: _split(x, 0))(x)
            b = jax.jit(segment.deinterleave, static_argnums=1)(x, FIELDS)
            bits = jax.lax.bitcast_convert_type
            diff = int(sum(jnp.sum(bits(p, jnp.uint32) != bits(q, jnp.uint32))
                           for p, q in zip(a, b)))
            del a, b
            line = {"shape": shape, "shift_vs_deinterleave_words": diff}
            print(json.dumps(line), flush=True)
        del xs
    return 0


if __name__ == "__main__":
    sys.exit(main())
