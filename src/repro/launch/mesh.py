"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax init; smoke tests must see
1 device).

Single pod: 256 chips as (16, 16) = ("data", "model") — v5e pod, 2D torus.
Multi-pod : 512 chips as (2, 16, 16) = ("pod", "data", "model"); the "pod"
axis is data-parallel by default (gradient reduction over DCI), or the
pipeline axis when pipeline parallelism is enabled (dist/pipeline_par.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.dist.sharding import ShardCtx


def _auto_mesh(shape, axes):
    """Mesh whose axes the compiler partitions (``AxisType.Auto``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_ctx(mesh, *, long_context: bool = False,
             fsdp: bool = False) -> ShardCtx:
    """ShardCtx for a production mesh (or None mesh for local tests)."""
    if mesh is None:
        return ShardCtx(mesh=None, data_axes=(), model_axis=None)
    names = mesh.axis_names
    data_axes = tuple(a for a in ("pod", "data") if a in names)
    if long_context:
        # batch=1: the batch dim cannot shard — activations replicate over
        # the data axes and the KV sequence dim is sharded instead.
        return ShardCtx(mesh=mesh, data_axes=(),
                        model_axis="model" if "model" in names else None,
                        seq_axes=tuple(a for a in ("data", "model")
                                       if a in names))
    return ShardCtx(
        mesh=mesh,
        data_axes=data_axes,
        model_axis="model" if "model" in names else None,
        seq_axes=(),
        fsdp=fsdp,
    )


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh over however many fake devices tests configured."""
    return _auto_mesh(shape, axes)
