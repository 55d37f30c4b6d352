"""Shared plumbing for the EARTH Pallas kernels.

Kernels are written for TPU (pl.pallas_call + BlockSpec VMEM tiling) and
validated on CPU with ``interpret=True`` — the kernel bodies use only
static-shape slice/pad/where ops, which lower to cheap VREG data movement on
real TPUs (see DESIGN.md §2).  Static-pattern kernels route via compiled
ShiftPlans (DESIGN.md §3): take-masks ride in as one stacked operand
(Pallas kernels cannot close over array constants) while shift amounts and
layer structure stay static Python in the kernel closure.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Row-tile height for 2-D kernels: one sublane group.
ROW_TILE = 8


@functools.cache
def interpret_mode() -> bool:
    """True when the default device is not a TPU (CPU test runs)."""
    return jax.devices()[0].platform != "tpu"


def flatten_rows(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    """(..., n) -> (R, n) plus the leading shape for unflattening."""
    lead = x.shape[:-1]
    r = 1
    for d in lead:
        r *= d
    return x.reshape(r, x.shape[-1]), lead


def pad_rows(x: jax.Array, tile: int = ROW_TILE) -> tuple[jax.Array, int]:
    """Pad axis 0 to a multiple of ``tile``; returns (padded, original_rows)."""
    r = x.shape[0]
    pad = (-r) % tile
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x, r


def row_grid(rows: int, tile: int = ROW_TILE) -> int:
    assert rows % tile == 0
    return rows // tile


def row_tile(rows: int, cap: int = 256) -> int:
    """Largest power-of-two multiple of ROW_TILE dividing ``rows``, capped.

    Whole-step fused super-transactions stack many accesses into one tall
    block; with a fixed 8-row tile the grid step count grows with the
    stack and both interpret-mode grid iteration and TPU grid dispatch
    scale with it.  A (cap, n) block stays far inside VMEM."""
    t = ROW_TILE
    while rows % (t * 2) == 0 and t * 2 <= cap:
        t *= 2
    return t


def tile_rows(x: jax.Array, cap: int = 256) -> tuple[jax.Array, int, int]:
    """Pad axis 0 and pick the row tile: (padded, original_rows, tile).

    On TPU: pad to ROW_TILE and tile up to ``cap`` rows (fewer grid
    dispatches, still pipelined).  Off-TPU (interpret mode) a grid step
    costs a full-buffer copy regardless of block height, so the whole
    padded block becomes ONE grid step (tile = rows padded to a power of
    two — at most 2x routing work, instead of rows/8 buffer copies)."""
    r = x.shape[0]
    if interpret_mode():
        tile = max(ROW_TILE, 1 << max(r - 1, 1).bit_length())
        pad = tile - r
        if pad:
            x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        return x, r, tile
    x, r = pad_rows(x)
    return x, r, row_tile(x.shape[0], cap)


def stack_plan_masks(plans) -> tuple:
    """Concat several plans' mask rows into ONE (S, n) int32 operand plus
    per-plan row spans — the single concatenated mask upload of a fused
    super-transaction (used by segment and multi-access strided kernels)."""
    import numpy as np

    from repro.core import shiftnet
    rows, spans = [], []
    for p in plans:
        r = shiftnet.plan_mask_stack(p)
        spans.append((len(rows), len(rows) + r.shape[0]))
        rows.extend(r)
    if not rows:
        return np.zeros((1, plans[0].n), np.int32), spans
    return np.stack(rows).astype(np.int32), spans


def plan_operands(plan):
    """(masks, valid, S) kernel operands for a compiled ShiftPlan.

    masks: (S, plan.n) int32 stacked take-masks, padded to one dummy row
    for empty plans (Pallas rejects zero-size blocks — apply_plan_operand
    consumes zero rows in that case).  valid: (1, plan.n) int32 occupancy.
    """
    import numpy as np

    from repro.core import shiftnet
    masks = shiftnet.plan_mask_stack(plan).astype(np.int32)
    if not masks.shape[0]:
        masks = np.zeros((1, plan.n), np.int32)
    valid = plan.valid.astype(np.int32).reshape(1, plan.n)
    return jnp.asarray(masks), jnp.asarray(valid), masks.shape[0]


def pytree_nbytes(tree) -> int:
    """Total payload bytes of a pytree of arrays (cache-memory accounting
    for the serving benchmarks and the paged-pool stats)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "dtype"))


def call(kernel, *, out_shape, grid, in_specs, out_specs, **kwargs):
    """pallas_call with the platform-appropriate interpret flag."""
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        interpret=interpret_mode(),
        **kwargs,
    )
