"""vx verbs — argument normalization over the spec→plan→program pipeline.

Since PR 4 this module contains NO executor closures: every verb (and
both ``_many`` forms) normalizes its operands, resolves the policy, and
then lowers through the ONE pipeline in ``repro.vx.lower``:

    spec  -> lower.lower(op, specs, impl, shard)   # a Program (vx/program.py)
          -> lower.executor(program, specs, shard) # compiled, PLANS-cached
          -> executor(*operands)

Programs are keyed by spec (dtype + vl included), resolved impl, and the
SHARD LAYOUT: passing ``shard=vx.Shard(axes, axis, mesh)`` lowers the
access shard-locally under ``shard_map`` (offset-rebased per-shard plans
for strided patterns, local lane permutation for segment transposition)
instead of slicing a sharded leaf globally.  ``core/accessfuse.py``'s
StepScheduler rides the same pipeline — its merge is the program-level
``vx.program.fuse`` pass.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.vx import lower as _lower
from repro.vx import program as _program
from repro.vx.policy import Policy, resolve
from repro.vx.spec import (BANK, AccessSpec, Compact, Indexed, Paged,
                           Segment, Strided)

Shard = _program.Shard


def _is_static(stride) -> bool:
    return isinstance(stride, (int, np.integer))


def _fold_routing(spec: Indexed, shift, valid) -> Indexed | None:
    """Promote a host-known (shift, valid) routing into the spec so the
    access compiles through the plan stage (constant take-masks, memoized
    under the spec key).  Traced operands return None (dynamic network)."""
    if spec.routing is not None:
        if shift is not None or valid is not None:
            raise ValueError(
                f"{spec} already folds a static routing; do not also pass "
                f"shift=/valid=")
        return spec
    host = (np.ndarray, list, tuple)
    if isinstance(shift, host) and isinstance(valid, host):
        return dataclasses.replace(
            spec, routing=(tuple(np.asarray(shift, np.int64).tolist()),
                           tuple(np.asarray(valid, bool).tolist())))
    return None


# ---------------------------------------------------------------------------
# gather / scatter (Strided, Indexed)
# ---------------------------------------------------------------------------

def _static_strided(spec: Strided, stride) -> Strided | None:
    """The spec with a compile-time stride folded in, or None if the
    stride is runtime (traced)."""
    if not spec.runtime:
        if stride is not None:
            raise ValueError(
                f"stride= was passed but {spec} already pins stride="
                f"{spec.stride}; use stride=vx.BANK in the spec for "
                f"call-time strides")
        return spec
    if stride is None:
        raise ValueError(
            "spec has stride=vx.BANK: pass the runtime stride as stride=")
    if _is_static(stride):
        return dataclasses.replace(spec, stride=int(stride))
    return None


def _bind_scales(spec: Paged, scales):
    """Fold the runtime ``scales`` operand into a quantized spec: its
    dtype becomes ``spec.scale_dtype``, so the quantized program is a
    DISTINCT plan-cache entry from the float one (spec fields are the
    cache key).  Validates presence both ways."""
    if scales is None:
        if spec.quantized:
            raise ValueError(f"{spec} is quantized: pass scales=")
        return spec
    if spec.scale_dtype is None:
        return dataclasses.replace(spec, scale_dtype=str(scales.dtype))
    return spec


def gather(spec: AccessSpec, buf: jax.Array, *, stride=None, shift=None,
           valid=None, table=None, scales=None, lead=None,
           policy: Policy | str | None = None,
           shard: Shard | None = None) -> jax.Array:
    """Dense read through the access described by ``spec``.

    * :class:`Strided` — ``(..., n) -> (..., vl)``; a ``stride=vx.BANK``
      spec takes the runtime stride via ``stride=`` and dispatches through
      the plan bank's ``lax.switch`` (compiled masks for banked strides,
      dynamic-count network otherwise; either sign engages the Reverser).
    * :class:`Indexed` — DROM gather with per-lane ``shift`` and ``valid``
      operands.  Host-known routings (numpy/list/tuple) are folded into
      the spec and compile through the plan stage (constant take-masks);
      traced operands take the dynamic-count network.
    * :class:`Paged` — page-table gather over a ``(*lead, P, ps, *trail)``
      pool: ``table=`` is the runtime ``(*batch, pages)`` int32 page
      table (entries ``< 0`` read as zeros); returns the gathered
      ``(*lead, *batch, pages*ps, *trail)`` sequences.  ``shard=`` (on
      the pool's page axis, ``Shard.axis == -(trail+2)``) gathers
      shard-locally from the owned page block and psum-merges — the
      sharded pool is never sliced globally.  A QUANTIZED pool passes
      its per-page scale tensor as ``scales=`` and returns dequantized
      float sequences from the same one-program gather.  ``lead=`` (a
      runtime index into the single lead axis of a pool stacked over
      layers) reads that one layer's pages from the stack in place.

    For the other specs ``shard=`` marks ``buf``'s lane axis as sharded:
    the access lowers to shard-local offset-rebased plans under
    ``shard_map`` (replicated output), never a global slice of the
    sharded leaf.
    """
    pol = resolve(policy)
    if isinstance(spec, Strided):
        spec = spec.bind(buf.dtype)
        static = _static_strided(spec, stride)
        if static is not None:
            return _lower.run("gather.plan", static, pol.impl, buf,
                              shard=shard)
        return _lower.run("bank.gather", spec, pol.impl, buf, stride,
                          shard=shard)
    if isinstance(spec, Paged):
        if table is None:
            raise ValueError("Paged gather needs the page table as table=")
        spec = _bind_scales(spec.bind(buf.dtype), scales)
        ops = (buf, scales, table) if spec.quantized else (buf, table)
        if lead is not None:
            if shard is not None:
                raise NotImplementedError("lead= with shard=")
            ops += (lead,)
        return _lower.run("paged.gather", spec, pol.impl, *ops,
                          shard=shard)
    if isinstance(spec, Indexed):
        spec = spec.bind(buf.dtype)
        static = _fold_routing(spec, shift, valid)
        if static is not None:
            return _lower.run("idx.gather", static, pol.impl, buf,
                              shard=shard)
        if shift is None or valid is None:
            raise ValueError("Indexed gather needs shift= and valid= "
                             "(or a spec with routing=)")
        return _lower.run("idx.gather", spec, pol.impl,
                          buf, shift, valid, shard=shard)
    raise TypeError(f"gather does not accept {type(spec).__name__} specs")


def scatter(spec: AccessSpec, buf: jax.Array, values: jax.Array, *,
            stride=None, shift=None, valid=None, table=None, pos=None,
            scales=None, lead=None, policy: Policy | str | None = None,
            shard: Shard | None = None):
    """Write/merge through the access described by ``spec``.

    * :class:`Strided` — merge dense ``values`` into strided positions of
      ``buf`` (read-modify-write; returns the updated window).  With
      ``shard=`` the window stays sharded: each shard merges only the
      value lanes it owns (rebased plan), no collective.
    * :class:`Paged` — the decode append: write one ``(*batch, *trail)``
      beat per table row into pool ``buf`` at per-row position ``pos=``
      through the page table ``table=`` (rows with ``pos < 0`` or an
      unallocated page entry are dropped); returns the updated pool.
      A QUANTIZED pool passes ``scales=`` and gets ``(pool, scales)``
      back — the beat quantizes on write and the page scale widens
      monotonically (see vx/lower.py).  A pool stacked over layers takes
      one beat per row for every layer, or ``(*lead, *batch, *trail)``
      values, each layer's own; ``lead=`` (a runtime layer index) writes
      that one layer of the stack in place.
    * :class:`Indexed` — raw DROM scatter of ``values`` (``buf`` is unused;
      pass None); returns ``(payload, occupancy)``.
    * :class:`Compact` — expansion (the compaction inverse): ``buf`` is the
      boolean mask, ``values`` the packed rows; returns rows scattered back
      to the mask positions, zeros elsewhere.
    """
    pol = resolve(policy)
    if isinstance(spec, Paged):
        if table is None or pos is None:
            raise ValueError("Paged scatter needs table= and pos=")
        spec = _bind_scales(spec.bind(buf.dtype), scales)
        ops = (buf, scales) if spec.quantized else (buf,)
        ops += (values, table, pos) + (() if lead is None else (lead,))
        return _lower.run("paged.scatter", spec, pol.impl, *ops,
                          shard=shard)
    if isinstance(spec, Strided):
        spec = spec.bind(buf.dtype)
        static = _static_strided(spec, stride)
        if static is not None:
            return _lower.run("scatter.plan", static, pol.impl, buf, values,
                              shard=shard)
        return _lower.run("bank.scatter", spec, pol.impl, buf, values,
                          stride, shard=shard)
    if isinstance(spec, Indexed):
        if shift is None or valid is None:
            raise ValueError("Indexed scatter needs shift= and valid=")
        return _lower.run("idx.scatter", spec.bind(values.dtype), pol.impl,
                          values, shift, valid, shard=shard)
    if isinstance(spec, Compact):
        return _lower.run("compact.expand", spec.bind(values.dtype),
                          pol.impl, values, buf, shard=shard)
    raise TypeError(f"scatter does not accept {type(spec).__name__} specs")


# ---------------------------------------------------------------------------
# transpose (Segment): AoS <-> SoA
# ---------------------------------------------------------------------------

def transpose(spec: Segment, x, *, policy: Policy | str | None = None,
              shard: Shard | None = None):
    """Segment transposition, direction inferred from the operand:

    * a single AoS array ``(..., n)`` -> list of ``fields`` SoA arrays
      ``(..., n/fields)`` (segment load / deinterleave),
    * a sequence of ``fields`` SoA arrays -> one AoS array (segment store /
      interleave).

    ``shard=`` (an OUTER axis, ``Shard.axis <= -2``) executes the lane
    permutation shard-locally under ``shard_map`` — the sharded operand is
    never gathered.
    """
    if not isinstance(spec, Segment):
        raise TypeError(f"transpose needs a Segment spec, got "
                        f"{type(spec).__name__}")
    pol = resolve(policy)
    if isinstance(x, (list, tuple)):
        parts = list(x)
        if len(parts) != spec.fields:
            raise ValueError(f"expected {spec.fields} fields, "
                             f"got {len(parts)}")
        return _lower.run("seg.int", spec.bind(parts[0].dtype), pol.impl,
                          parts, shard=shard)
    if x.shape[-1] != spec.n:
        raise ValueError(f"AoS beat has {x.shape[-1]} lanes, spec.n is "
                         f"{spec.n}")
    return _lower.run("seg.deint", spec.bind(x.dtype), pol.impl, x,
                      shard=shard)


# ---------------------------------------------------------------------------
# compact (Compact): masked compaction / packed indices
# ---------------------------------------------------------------------------

def compact(spec: Compact, mask: jax.Array, rows: jax.Array | None = None,
            *, policy: Policy | str | None = None):
    """Order-preserving masked compaction.

    With ``rows`` — pack the masked rows to the front; returns
    ``(packed_rows, packed_valid)``, truncated to ``spec.capacity`` rows
    when ``cap`` is set.  Without ``rows`` — return the packed *indices*
    of set mask bits (first ``spec.capacity`` kept), the MoE dispatch
    primitive (runtime-count plan-bank member; no conflict reductions)."""
    if not isinstance(spec, Compact):
        raise TypeError(f"compact needs a Compact spec, got "
                        f"{type(spec).__name__}")
    pol = resolve(policy)  # validate even on the impl-independent path
    if rows is None:
        return _lower.run("compact.ids", spec, pol.impl, mask)
    return _lower.run("compact.rows", spec.bind(rows.dtype), pol.impl,
                      rows, mask)


# ---------------------------------------------------------------------------
# batched forms: one launch for a whole step's same-shape accesses
# ---------------------------------------------------------------------------

def gather_many(specs, bufs, *, table=None, scales=None,
                policy: Policy | str | None = None,
                shard: Shard | None = None):
    """Whole-step batched gather — ONE kernel launch, one mask operand.

    * ``specs`` a sequence of :class:`Strided` sharing (n, vl) with
      per-access (stride, offset), ``bufs`` the matching windows (a
      sequence, or an already-stacked ``(A, ..., n)`` array): the fused
      concatenated-mask transaction.  Returns the stacked ``(A, ..., vl)``.
    * ``specs`` a single :class:`Segment`, ``bufs`` a sequence of
      same-shape AoS arrays: the step-fused segment load (``shard=``
      supported: the stacked group transposes shard-locally).  Returns one
      field list per input array.
    * ``specs`` a single :class:`Paged`, ``bufs`` a sequence of same-shape
      pools sharing one runtime ``table=``: the whole-step paged read —
      all pools stack and the heterogeneous per-request lengths (encoded
      in the table rows) fuse into ONE page-granular gather program
      (``shard=`` supported on the page axis).  Quantized pools pass
      their per-page scale tensors as ``scales=`` (stacked the same
      way); the dequant rides the SAME single program.  Returns one
      gathered array per pool.
    """
    pol = resolve(policy)
    if isinstance(specs, Paged):
        if table is None:
            raise ValueError("Paged gather_many needs table=")
        pools = list(bufs)
        scl = None if scales is None else list(scales)
        spec = _bind_scales(specs.bind(pools[0].dtype),
                            None if scl is None else scl[0])
        prog = _program.fuse([_lower.lower("paged.gather", spec, pol.impl,
                                           shard)] * len(pools))
        stacked = pools[0] if len(pools) == 1 else jnp.stack(pools)
        exe = _lower.executor(prog, (spec,) * len(pools), shard)
        if scl is not None:
            sstk = scl[0] if len(scl) == 1 else jnp.stack(scl)
            out = exe(stacked, sstk, table)
        else:
            out = exe(stacked, table)
        return [out] if len(pools) == 1 else [out[a]
                                              for a in range(len(pools))]
    if isinstance(specs, Segment):
        aos_list = list(bufs)
        spec = specs.bind(aos_list[0].dtype)
        prog = _program.fuse([_lower.lower("seg.deint", spec, pol.impl,
                                           shard)] * len(aos_list))
        stacked = (aos_list[0] if len(aos_list) == 1
                   else jnp.stack(aos_list))
        outs = _lower.executor(prog, (spec,) * len(aos_list), shard)(stacked)
        if len(aos_list) == 1:
            return [list(outs)]
        return [[o[a] for o in outs] for a in range(len(aos_list))]
    specs = list(specs)
    if not specs or not all(isinstance(s, Strided) for s in specs):
        raise TypeError("gather_many needs Strided specs or one Segment")
    if len({s.vl for s in specs}) != 1 or len({s.n for s in specs}) != 1:
        raise ValueError("fused gather needs one shared (n, vl)")
    windows = bufs if isinstance(bufs, jax.Array) else jnp.stack(list(bufs))
    specs = tuple(s.bind(windows.dtype) for s in specs)
    prog = _program.fuse([_lower.lower("gather.plan", s, pol.impl, shard)
                          for s in specs])
    return _lower.executor(prog, specs, shard)(windows)


def scatter_many(spec: Segment, groups: Sequence[Sequence[jax.Array]], *,
                 policy: Policy | str | None = None,
                 shard: Shard | None = None) -> list[jax.Array]:
    """Step-fused segment store: A same-shape SoA groups, ONE launch.
    Returns one AoS array per group."""
    if not isinstance(spec, Segment):
        raise TypeError("scatter_many needs a Segment spec")
    pol = resolve(policy)
    groups = [list(g) for g in groups]
    nf = spec.fields
    spec = spec.bind(groups[0][0].dtype)
    prog = _program.fuse([_lower.lower("seg.int", spec, pol.impl,
                                       shard)] * len(groups))
    fn = _lower.executor(prog, (spec,) * len(groups), shard)
    if len(groups) == 1:
        return [fn(groups[0])]
    stacked = [jnp.stack([g[f] for g in groups]) for f in range(nf)]
    out = fn(stacked)
    return [out[a] for a in range(len(groups))]


# ---------------------------------------------------------------------------
# warm-up: precompile the plan bank for a window width
# ---------------------------------------------------------------------------

def warm(n: int, *, offset: int = 0, vl: int | None = None,
         strided: bool = True, fields: tuple | None = None,
         policy: Policy | str | None = None) -> None:
    """Precompile runtime-stride bank plans and segment plans for a window
    width (one-time host cost, so the first step never pays plan
    compilation).  ``strided=False`` skips the +-stride slots — serving
    only consults the segment plans (the KV FIELD=2 split).

    Resolves ``policy`` exactly like the verbs (explicit arg > innermost
    ``vx.use`` scope > env/platform default), so prewarming compiles the
    plans the governing policy will actually hit: bank slots are warmed
    only when the policy carries a non-empty ``bank_strides`` set (the
    bank itself always compiles the full :data:`~repro.vx.policy.
    BANK_STRIDES` slot layout — its ``lax.switch`` shape is fixed), and
    segment plans are skipped entirely under ``impl="ref"`` (the XLA path
    never consults them)."""
    from repro.core import accessfuse
    from repro.vx.policy import BANK_FIELDS
    pol = resolve(policy)
    fields = BANK_FIELDS if fields is None else fields
    if pol.impl == "ref":
        fields = ()
    accessfuse.warm(n, offset=offset, vl=vl,
                    strided=strided and bool(pol.bank_strides),
                    fields=fields)
