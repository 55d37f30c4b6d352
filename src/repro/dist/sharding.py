"""Sharding rules: one ShardCtx object carries the mesh + axis roles; every
PartitionSpec in the system is derived here (params, activations, optimizer
state) so that elastic restore / dry-run / serving all agree on placement.

Axis roles
----------
* ``data_axes``  : batch dimension of activations; gradient all-reduce.
* ``model_axis`` : tensor parallelism (Megatron column/row splits, expert
                   parallelism, vocab-sharded logits).
* ``seq_axes``   : long-context serving only (B=1): KV sequence dim sharded.

Spec derivation is *rule-based on leaf path + shape* (not stored per-leaf),
so checkpoints hold logical arrays and any mesh can rebuild placements
(ft/elastic.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# ShardCtx
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh + axis-role bundle threaded through models/train/serve."""
    mesh: Any
    data_axes: tuple = ()
    model_axis: str | None = None
    seq_axes: tuple = ()
    fsdp: bool = False

    # -- sizes ---------------------------------------------------------------
    def _axis_size(self, axis) -> int:
        if self.mesh is None or axis is None:
            return 1
        return self.mesh.shape[axis]

    @property
    def model_size(self) -> int:
        return self._axis_size(self.model_axis)

    @property
    def data_size(self) -> int:
        return math.prod(self._axis_size(a) for a in self.data_axes) \
            if self.data_axes else 1

    @property
    def seq_shard_acts(self) -> bool:
        """Megatron-SP: sequence-shard the residual stream between blocks."""
        return self.mesh is not None and self.model_axis is not None

    # -- spec helpers --------------------------------------------------------
    def model_if_divisible(self, dim: int):
        """model_axis iff ``dim`` splits evenly across it, else None."""
        if (self.mesh is None or self.model_axis is None or dim is None
                or dim % self.model_size or dim < self.model_size):
            return None
        return self.model_axis

    def batch_spec(self, *rest) -> P:
        """P for a batch-leading activation: (B over data axes, *rest)."""
        return P(self.data_axes if self.data_axes else None, *rest)

    def sharding(self, spec: P) -> NamedSharding:
        assert self.mesh is not None, "sharding() needs a mesh"
        return NamedSharding(self.mesh, spec)

    def constrain(self, x, spec: P):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.sharding(spec))

    def vx_seq_shard(self, axis: int = -3):
        """``vx.Shard`` placement annotation for a buffer axis sharded
        over this context's sequence axes (long-context serving: B=1, the
        KV sequence dim takes every axis).  ``axis`` counts from the end
        (the default -3 is the sequence dim of an (NS, B, Sc, K, 2D)
        cache leaf).  None when mesh-less or no axis plays the sequence
        role — callers then take the replicated lowering."""
        if self.mesh is None:
            return None
        axes = self.seq_axes or (self.data_axes
                                 + ((self.model_axis,)
                                    if self.model_axis else ()))
        if not axes:
            return None
        from repro.vx.program import Shard
        return Shard(axes=tuple(axes), axis=axis, mesh=self.mesh)

    def vx_pool_shard(self, axis: int = -4):
        """``vx.Shard`` annotation for a PAGED-POOL leaf sharded on its
        page axis (serving: the shared KV page pool is the memory
        ceiling, so its physical pages spread across the mesh and
        ``vx.Paged`` gathers run shard-locally on the owned page block —
        the pool is never sliced globally).  Same axis-role selection as
        :meth:`vx_seq_shard`; the default -4 is the page axis of an
        ``(NS, P, page_size, K, 2D)`` pool leaf."""
        return self.vx_seq_shard(axis)


def local_ctx() -> ShardCtx:
    """Single-process / single-device context (mesh-less no-op specs)."""
    return ShardCtx(mesh=None, data_axes=(), model_axis=None)


# ---------------------------------------------------------------------------
# Parameter spec rules
# ---------------------------------------------------------------------------

def _kp_str(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def _pick_model_dim(path: str, shape: tuple, start: int, ctx: ShardCtx):
    """Dim index to place the model axis on, or None.

    Rules (checked in order):
      * MoE expert banks (wg/wu/wo under a moe subtree, >= 3 trailing dims):
        shard the EXPERT dim — expert parallelism, matching the shard_map
        in_specs of models/moe.py.
      * attention/FFN output projections named ``wo``: shard the INPUT
        (row-parallel — the matching all-reduce is the FFN psum).
      * otherwise: the largest trailing dim divisible by the model size
        (column-parallel default; embed/lm_head land vocab-sharded, which
        is what the sharded cross-entropy in models/transformer.py expects).
    """
    ms = ctx.model_size
    nd = len(shape)
    if nd - start < 2:          # vectors (norm gains, biases): replicate
        return None

    def ok(i):
        return shape[i] % ms == 0 and shape[i] >= ms

    leaf = path.rsplit("/", 1)[-1]
    if "moe" in path and leaf in ("wg", "wu", "wo") and nd - start >= 3:
        if ok(start):
            return start
    if leaf == "wo" and nd - start == 2 and ok(start):
        return start
    if leaf == "wkv" and nd - start == 2:
        # the interleaved [k|v] beat (EARTH AoS unit) must stay contiguous
        # per device — shard the INPUT dim instead of splitting the beat
        # (splitting it also trips an XLA SPMD partitioner miscompile with
        # the strided deinterleave reshape on some backends; measured)
        return start if ok(start) else None
    best = None
    for i in range(start, nd):
        if ok(i) and (best is None or shape[i] >= shape[best]):
            best = i
    return best


def param_spec(path: str, shape: tuple, ctx: ShardCtx) -> P:
    """PartitionSpec for one parameter leaf."""
    if ctx.mesh is None:
        return P()
    # block stacks carry a leading superblock dim that must never shard
    # (it is the lax.scan carry axis)
    stacked = path.startswith("blocks") or "/blocks/" in path
    start = 1 if (stacked and len(shape) >= 2) else 0
    parts: list = [None] * len(shape)
    if ctx.model_axis is not None:
        md = _pick_model_dim(path, shape, start, ctx)
        if md is not None:
            parts[md] = ctx.model_axis
    spec = P(*parts)
    if ctx.fsdp:
        spec = add_data_sharding(spec, shape, ctx, start=start)
    return spec


def tree_param_specs(params, ctx: ShardCtx):
    """Pytree of PartitionSpecs mirroring ``params``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [param_spec(_kp_str(kp), tuple(leaf.shape), ctx)
             for kp, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def add_data_sharding(spec: P, shape: tuple, ctx: ShardCtx, *,
                      start: int = 0) -> P:
    """Additionally shard ``spec`` over the data axes (ZeRO-1 / FSDP).

    Picks the first dim >= ``start`` that is unsharded and splits evenly
    across the combined data axes; returns ``spec`` unchanged when none fits.
    """
    if ctx.mesh is None or not ctx.data_axes:
        return spec
    ds = ctx.data_size
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i in range(start, len(shape)):
        if parts[i] is None and shape[i] % ds == 0 and shape[i] >= ds:
            parts[i] = ctx.data_axes if len(ctx.data_axes) > 1 \
                else ctx.data_axes[0]
            return P(*parts)
    return spec


# ---------------------------------------------------------------------------
# Misc helpers used by launch / tests
# ---------------------------------------------------------------------------

def replicate(x, ctx: ShardCtx):
    """Fully replicate a pytree on ctx's mesh (no-op mesh-less)."""
    if ctx.mesh is None:
        return x
    return jax.tree.map(
        lambda a: jax.device_put(a, ctx.sharding(P())), x)


def spec_tree_shardings(specs, ctx: ShardCtx):
    """Map a PartitionSpec pytree to NamedShardings."""
    if ctx.mesh is None:
        return None
    return jax.tree.map(lambda s: ctx.sharding(s), specs,
                        is_leaf=lambda x: isinstance(x, P))
