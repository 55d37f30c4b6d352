"""Segment (AoS <-> SoA) Pallas kernels — compiled bulk transposition.

A segment access with FIELDS=f over an n-lane beat is ONE lane permutation
(AoS -> concatenated SoA fields, or back).  A segment LOAD of 32-bit words
whose rows come in 128-row chunks takes the TRANSPOSE route (the column-wise
access of EARTH's shifted register bank, done by the TPU's transpose unit):
each chunk is transposed into a VMEM scratch, lanes becoming sublanes, and
field f is the sublane-strided read ``f, f+fields, ...`` transposed back —
no lane shifts at all.  Every other access routes through the static-plan
compiler (core/shiftplan.py) in a SINGLE kernel either as

  * a FUSED permutation pass — one O(log n) Benes/butterfly sweep of static
    shifts + constant-mask selects handling ALL fields at once (the RCVRF
    shifted-register-bank bulk transposition, EARTH §4.5), or
  * ``fields`` compiled per-field passes when the cost model says that is
    cheaper (small field counts) — still pruned single-shift layers with
    constant masks, never the dynamic triple-shift loop.

The shift routes allocate no scratch "segment buffer": each field's lanes
are sliced straight out of the routed beat into its output block
(immediate writeback, Fig. 4c).  ``fused=False`` keeps the per-field
dynamic-count networks as the fallback/oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import scg, shiftnet, shiftplan
from repro.kernels import _common
from repro.vx.cache import SEGMENT_LOADS


# One concatenated (S, n) mask operand for several plans (shared helper).
_stack_masks = _common.stack_plan_masks


# ---------------------------------------------------------------------------
# Routing bodies (pure jnp — shared by the Pallas kernels and benchmarks)
# ---------------------------------------------------------------------------

def route_deinterleave(aos, masks, mode: str, plans, spans, fields: int):
    """(rows, n) AoS -> list of (rows, m) fields via compiled plans."""
    n = aos.shape[-1]
    m = n // fields
    if mode == "fused":
        plan = plans[0]
        x = aos if plan.n == n else jnp.pad(aos, ((0, 0), (0, plan.n - n)))
        lo, hi = spans[0]
        routed = shiftnet.apply_plan_operand(x, masks[lo:hi], plan, axis=-1)
        return [jax.lax.slice(routed, (0, f * m), (aos.shape[0], (f + 1) * m))
                for f in range(fields)]
    outs = []
    for f, plan in enumerate(plans):
        lo, hi = spans[f]
        routed = shiftnet.apply_plan_operand(aos, masks[lo:hi], plan,
                                             axis=-1)
        outs.append(jax.lax.slice(routed, (0, 0), (aos.shape[0], m)))
    return outs


def route_interleave(x, masks, valid, mode: str, plans, spans, fields: int):
    """(rows, n) concatenated SoA -> (rows, n) AoS beat."""
    rows, n = x.shape
    if mode == "fused":
        plan = plans[0]
        xp = x if plan.n == n else jnp.pad(x, ((0, 0), (0, plan.n - n)))
        lo, hi = spans[0]
        routed = shiftnet.apply_plan_operand(xp, masks[lo:hi], plan, axis=-1)
        return jax.lax.slice(routed, (0, 0), (rows, n))
    m = n // fields
    acc = jnp.zeros((rows, n), x.dtype)
    for f, plan in enumerate(plans):
        lo, hi = spans[f]
        fx = jax.lax.slice(x, (0, f * m), (rows, (f + 1) * m))
        padded = jnp.pad(fx, ((0, 0), (0, n - m)))
        routed = shiftnet.apply_plan_operand(padded, masks[lo:hi], plan,
                                             axis=-1)
        acc = jnp.where(valid[f][None, :] != 0, routed, acc)
    return acc


# ---------------------------------------------------------------------------
# Deinterleave (segment load)
# ---------------------------------------------------------------------------

def _deint_plan_kernel(masks_ref, aos_ref, *o_refs, mode, plans, spans,
                       fields: int):
    outs = route_deinterleave(aos_ref[...], masks_ref[...], mode, plans,
                              spans, fields)
    for f in range(fields):
        o_refs[f][...] = outs[f]


def _deint_dyn_kernel(aos_ref, *o_refs, fields: int):
    aos = aos_ref[...]
    n = aos.shape[-1]
    m = n // fields
    for f in range(fields):
        shift, valid = scg.gather_counts(n, fields, f, m)
        res = shiftnet.gather_network(aos, shift[None, :], valid[None, :],
                                      axis=-1)
        o_refs[f][...] = jax.lax.slice(res.payload, (0, 0), (aos.shape[0], m))


# Transpose route.  One chunk is one (128, n) -> (n, 128) transpose: a
# strided read needs a scratch whose last dim is exactly 128 lanes.
_CHUNK = 128
# Input block bytes: a step moves this in and the same out.  At n = 256 on
# a v5e, 1024-row blocks split the decode step's pool at 601 GB/s against
# 388 GB/s for 128 rows, and 2048 rows gain nothing (PERF.md §6).
_BLOCK_BYTES = 1024 * 1024
# VMEM a step may take: input and output blocks double-buffered (4x the
# input block) plus the transposed chunks (1x), inside v5e's 16 MiB default.
_VMEM_BYTES = 12 * 1024 * 1024


def transpose_block_rows(rows: int, n: int, fields: int, dtype) -> int:
    """Block height of the transpose route for an (rows, n) segment load,
    or 0 where the route does not apply and the shift plans route it.

    The route needs 32-bit words, whole 128-row chunks, an n of whole
    128-lane vregs and fields of whole 8-sublane tiles; a block of one
    chunk must fit the VMEM budget (a GLU split at n = 6144 does not).
    The height is the largest power-of-two number of chunks dividing
    ``rows`` within ``_BLOCK_BYTES`` (at least one chunk)."""
    m = n // fields
    word = jnp.dtype(dtype).itemsize
    if (word != 4 or rows % _CHUNK or n % 128 or m % 8
            or 5 * _CHUNK * n * word > _VMEM_BYTES):
        return 0
    rt = _CHUNK
    while rows % (2 * rt) == 0 and 2 * rt * n * word <= _BLOCK_BYTES:
        rt *= 2
    return rt


def _deint_transpose_kernel(aos_ref, *refs, fields: int):
    o_refs, t_ref = refs[:fields], refs[fields]
    n = aos_ref.shape[-1]
    m = n // fields
    for c in range(t_ref.shape[0]):        # static: _BLOCK_BYTES bounds it
        rows = pl.ds(c * _CHUNK, _CHUNK)
        t_ref[c] = aos_ref[rows, :].T                   # lanes -> sublanes
        for f in range(fields):
            o_refs[f][rows, :] = t_ref[c, pl.ds(f, m, stride=fields), :].T


def _deint_transpose(flat: jax.Array, fields: int, rt: int
                     ) -> list[jax.Array]:
    """(R, n) 32-bit AoS -> fields x (R, m) in row blocks of ``rt``."""
    r, n = flat.shape
    m = n // fields
    return _common.call(
        functools.partial(_deint_transpose_kernel, fields=fields),
        out_shape=tuple(jax.ShapeDtypeStruct((r, m), flat.dtype)
                        for _ in range(fields)),
        grid=(r // rt,),
        in_specs=[pl.BlockSpec((rt, n), lambda i: (i, 0))],
        out_specs=tuple(pl.BlockSpec((rt, m), lambda i: (i, 0))
                        for _ in range(fields)),
        scratch_shapes=[pltpu.VMEM((rt // _CHUNK, n, _CHUNK), flat.dtype)],
    )(flat)


def _deint_shift(flat: jax.Array, fields: int, fused: bool
                 ) -> list[jax.Array]:
    """(R, n) AoS -> fields x (R, m) through the shift networks: the
    cost-modeled plans (``fused``) or the dynamic-count oracle."""
    n = flat.shape[-1]
    m = n // fields
    flat, r0, rt = _common.tile_rows(flat)
    grid = (_common.row_grid(flat.shape[0], rt),)
    out_shape = tuple(jax.ShapeDtypeStruct((flat.shape[0], m), flat.dtype)
                      for _ in range(fields))
    out_specs = tuple(pl.BlockSpec((rt, m), lambda i: (i, 0))
                      for _ in range(fields))
    if fused:
        mode, plans = shiftplan.segment_deinterleave_plans(n, fields)
        SEGMENT_LOADS.add(mode)
        masks, spans = _stack_masks(plans)
        S, W = masks.shape
        outs = _common.call(
            functools.partial(_deint_plan_kernel, mode=mode, plans=plans,
                              spans=spans, fields=fields),
            out_shape=out_shape,
            grid=grid,
            in_specs=[pl.BlockSpec((S, W), lambda i: (0, 0)),
                      pl.BlockSpec((rt, n), lambda i: (i, 0))],
            out_specs=out_specs,
        )(jnp.asarray(masks), flat)
    else:
        SEGMENT_LOADS.add("dynamic")
        outs = _common.call(
            functools.partial(_deint_dyn_kernel, fields=fields),
            out_shape=out_shape,
            grid=grid,
            in_specs=[pl.BlockSpec((rt, n), lambda i: (i, 0))],
            out_specs=out_specs,
        )(flat)
    return [o[:r0] for o in outs]


def deinterleave(aos: jax.Array, fields: int, *,
                 fused: bool = True) -> list[jax.Array]:
    """(..., fields*m) -> fields x (..., m)   (segment load).

    ``fused`` takes the transpose route where
    :func:`transpose_block_rows` accepts the shape, else the cost-modeled
    shift plans; ``fused=False`` runs the dynamic networks (the oracle).
    Each call counts its route in :data:`repro.vx.cache.SEGMENT_LOADS`."""
    n = aos.shape[-1]
    assert n % fields == 0
    flat, lead = _common.flatten_rows(aos)
    rt = transpose_block_rows(flat.shape[0], n, fields, aos.dtype) \
        if fused else 0
    if rt:
        SEGMENT_LOADS.add("transpose")
        outs = _deint_transpose(flat, fields, rt)
    else:
        outs = _deint_shift(flat, fields, fused)
    return [o.reshape(lead + (n // fields,)) for o in outs]


def deinterleave_many(aos_list: list[jax.Array], fields: int, *,
                      fused: bool = True) -> list[list[jax.Array]]:
    """Step-fused segment load: A same-shape AoS arrays in ONE launch.

    The stack rides through :func:`deinterleave` as a new leading dim, so
    the whole group shares one kernel launch and one mask upload (the
    whole-step analogue of the batched LSDO transaction block)."""
    outs = deinterleave(jnp.stack(aos_list), fields, fused=fused)
    return [[o[a] for o in outs] for a in range(len(aos_list))]


# ---------------------------------------------------------------------------
# Interleave (segment store)
# ---------------------------------------------------------------------------

def _int_plan_kernel(masks_ref, valid_ref, *refs, mode, plans, spans,
                     fields: int):
    f_refs, o_ref = refs[:-1], refs[-1]
    x = jnp.concatenate([r[...] for r in f_refs], axis=-1)  # (rt, n)
    o_ref[...] = route_interleave(x, masks_ref[...], valid_ref[...], mode,
                                  plans, spans, fields)


def _int_dyn_kernel(*refs, fields: int):
    f_refs, o_ref = refs[:-1], refs[-1]
    rt, m = f_refs[0].shape
    n = m * fields
    acc = jnp.zeros((rt, n), f_refs[0].dtype)
    for f in range(fields):
        padded = jnp.pad(f_refs[f][...], ((0, 0), (0, n - m)))
        shift, valid = scg.scatter_counts(n, fields, f, m)
        res = shiftnet.scatter_network(padded, shift[None, :], valid[None, :],
                                       axis=-1)
        acc = jnp.where(res.valid, res.payload, acc)
    o_ref[...] = acc


def interleave(soa: list[jax.Array], *, fused: bool = True) -> jax.Array:
    """fields x (..., m) -> (..., fields*m)   (segment store)."""
    fields = len(soa)
    m = soa[0].shape[-1]
    n = m * fields
    flats = []
    r0 = lead = rt = None
    for t in soa:
        f, lead = _common.flatten_rows(t)
        f, r0, rt = _common.tile_rows(f)
        flats.append(f)
    grid = (_common.row_grid(flats[0].shape[0], rt),)
    out_shape = jax.ShapeDtypeStruct((flats[0].shape[0], n), soa[0].dtype)
    f_specs = [pl.BlockSpec((rt, m), lambda i: (i, 0))
               for _ in range(fields)]
    if fused:
        mode, plans = shiftplan.segment_interleave_plans(n, fields)
        masks, spans = _stack_masks(plans)
        S, W = masks.shape
        valid = np.stack([p.valid for p in plans]).astype(np.int32) \
            if mode == "per_field" else np.zeros((1, n), np.int32)
        out = _common.call(
            functools.partial(_int_plan_kernel, mode=mode, plans=plans,
                              spans=spans, fields=fields),
            out_shape=out_shape,
            grid=grid,
            in_specs=[pl.BlockSpec((S, W), lambda i: (0, 0)),
                      pl.BlockSpec(valid.shape, lambda i: (0, 0))] + f_specs,
            out_specs=pl.BlockSpec((rt, n), lambda i: (i, 0)),
        )(jnp.asarray(masks), jnp.asarray(valid), *flats)
    else:
        out = _common.call(
            functools.partial(_int_dyn_kernel, fields=fields),
            out_shape=out_shape,
            grid=grid,
            in_specs=f_specs,
            out_specs=pl.BlockSpec((rt, n), lambda i: (i, 0)),
        )(*flats)
    return out[:r0].reshape(lead + (n,))
