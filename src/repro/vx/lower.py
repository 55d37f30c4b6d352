"""Lowering — stage transitions of the vx pipeline, and program execution.

``lower()`` takes (op, specs, impl, placement) and emits a validated
:class:`~repro.vx.program.Program`; ``executor()`` compiles a program into
the callable that actually runs it, memoized in the unified plan cache
under ``Program.key()`` — which includes dtype, vl, the resolved impl AND
the shard layout, so the same spec lowered against two placements yields
two distinct cached programs.

Replicated programs lower exactly where the PR 3 dispatch closures did:
``kernels/ref.py`` (XLA oracles), ``kernels/strided.py`` /
``kernels/segment.py`` / ``kernels/moe_compact.py`` /
``kernels/shift_{gather,scatter}.py`` (compiled-plan Pallas), and
``core/accessfuse.py`` (runtime-stride plan bank, compaction counts).

Sharded programs are the new arm: when the operand is sharded on the
accessed axis (``Shard.axis == -1`` for strided patterns) the program is
rewritten to SHARD-LOCAL plans — per-shard offset-rebased sub-specs from
``shiftplan.shard_strided_rows`` — executed under ``shard_map`` with a
``lax.switch`` over the shard index, plus one ``psum`` to merge the
disjoint output lanes (gather) or none at all (scatter: the window stays
sharded).  Lane-permutation programs (segment transposition) sharded on
any OTHER axis execute shard-locally with the unmodified plan.  Either
way the sharded leaf is never sliced globally, so SPMD never
rematerializes it — the lowering is co-designed with the physical
distribution of the buffer, the way Ara co-designs the memory datapath
with the banked register file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.vx import program as prg
from repro.vx.cache import PLANS
from repro.vx.spec import AccessSpec, Paged, Strided

#: Ops that accept a sharded placement, and where the shard axis may sit.
_SHARDABLE = {
    "gather.plan": "lane",      # Shard.axis == -1: offset-rebased plans
    "scatter.plan": "lane",
    "seg.deint": "outer",       # Shard.axis != -1: shard-local permutation
    "seg.int": "outer",
    "paged.gather": "pool",     # Shard.axis == -(trail+2): the page axis
}


def lower(op: str, specs, impl: str,
          shard: "prg.Shard | None" = None) -> prg.Program:
    """Build and validate the program for one access (width = len(specs))."""
    if isinstance(specs, AccessSpec):
        specs = (specs,)
    specs = tuple(specs)
    if shard is not None:
        where = _SHARDABLE.get(op)
        if where is None:
            raise NotImplementedError(
                f"{op} has no sharded lowering (got shard={shard})")
        if where == "lane":
            if shard.axis != -1:
                raise ValueError(
                    f"{op} shards the accessed lane axis: Shard.axis must "
                    f"be -1, got {shard.axis}")
            for s in specs:
                if s.runtime:
                    raise NotImplementedError(
                        "runtime-stride bank dispatch over a sharded "
                        "window is not lowered; pin the stride or gather "
                        "replicated")
                if not shard.divides(s.n):
                    raise ValueError(
                        f"window of {s.n} lanes does not split into "
                        f"{shard.nshards} equal shards")
            if len(specs) != 1:
                raise NotImplementedError(
                    "fused strided transactions have no sharded lowering")
        elif where == "pool":
            want = -(specs[0].trail + 2)
            if shard.axis != want:
                raise ValueError(
                    f"{op} shards the page-pool axis: Shard.axis must be "
                    f"{want} for trail={specs[0].trail}, got {shard.axis}")
            if len(set(specs)) != 1:
                raise NotImplementedError(
                    "heterogeneous fused paged transactions have no "
                    "sharded lowering")
        elif shard.axis == -1:
            raise ValueError(
                f"{op} permutes the lane axis; shard an outer axis "
                f"(Shard.axis <= -2), not the beat itself")
    return prg.single(op, specs, impl, shard)


def executor(program: prg.Program, specs,
             shard: "prg.Shard | None" = None):
    """The compiled callable for ``program`` (one entry per program key).

    ``specs`` are the live AccessSpec objects in transaction order (the
    program itself carries only their keys); ``shard`` the live placement
    matching the transaction layout.
    """
    if isinstance(specs, AccessSpec):
        specs = (specs,)
    txn = program.txn
    specs = tuple(specs)
    return PLANS.get(program.key(), lambda: _build(txn, specs, shard))


def run(op: str, spec: AccessSpec, impl: str, *operands,
        shard: "prg.Shard | None" = None):
    """lower + compile + execute in one call (the verb tail)."""
    program = lower(op, spec, impl, shard)
    return executor(program, spec, shard)(*operands)


# ---------------------------------------------------------------------------
# Builders: replicated lowerings (the PR 3 closures, now program-keyed)
# ---------------------------------------------------------------------------

def _build(txn: prg.Txn, specs: tuple, shard):
    if txn.layout is not None:
        return _build_sharded(txn, specs, shard)
    build = _BUILDERS[txn.op]
    return build(txn, specs)


def _gather_plan(txn: prg.Txn, specs: tuple):
    if txn.width > 1:
        return _gather_fused(txn, specs)
    spec, impl = specs[0], txn.impl
    s, o, vl = spec.stride, spec.offset, spec.vl
    if s < 0:
        from repro.core import accessfuse
        return lambda w: accessfuse.bank_gather_strided(w, s, o, vl)
    if impl == "ref":
        from repro.kernels import ref
        return lambda w: ref.gather_strided(w, s, o, vl)
    from repro.kernels import strided
    return lambda w: strided.gather_strided(w, s, o, vl,
                                            compiled=impl == "pallas")


def _gather_fused(txn: prg.Txn, specs: tuple):
    """Width-N strided super-transaction over a stacked (N, ..., n) window:
    one shared plan when homogeneous, the concatenated-mask kernel when
    heterogeneous, a stacked XLA loop under ref."""
    vl = specs[0].vl
    pairs = tuple((s.stride, s.offset) for s in specs)
    if txn.homogeneous:
        inner = _gather_plan(prg.Txn("gather.plan", txn.specs[:1], txn.impl),
                             specs[:1])
        return inner
    if txn.impl == "ref":
        from repro.kernels import ref

        def ref_many(windows):
            return jnp.stack([ref.gather_strided(windows[a], s, o, vl)
                              for a, (s, o) in enumerate(pairs)])

        return ref_many
    from repro.kernels import strided
    return lambda windows: strided.gather_strided_fused(
        windows, pairs, vl, compiled=txn.impl == "pallas")


def _scatter_plan(txn: prg.Txn, specs: tuple):
    spec, impl = specs[0], txn.impl
    s, o = spec.stride, spec.offset
    if s < 0:
        from repro.core import accessfuse
        return lambda w, v: accessfuse.bank_scatter_strided(w, v, s, o)
    if impl == "ref":
        from repro.kernels import ref
        return lambda w, v: ref.scatter_strided(w, v, s, o)
    from repro.kernels import strided
    return lambda w, v: strided.scatter_strided(w, v, s, o,
                                                compiled=impl == "pallas")


def _bank_gather(txn: prg.Txn, specs: tuple):
    spec = specs[0]
    from repro.core import accessfuse
    return lambda w, stride: accessfuse.bank_gather_strided(
        w, stride, spec.offset, spec.vl)


def _bank_scatter(txn: prg.Txn, specs: tuple):
    spec = specs[0]
    from repro.core import accessfuse
    return lambda w, v, stride: accessfuse.bank_scatter_strided(
        w, v, stride, spec.offset)


def _seg_deint(txn: prg.Txn, specs: tuple):
    fields, impl = specs[0].fields, txn.impl
    if impl == "ref":
        from repro.kernels import ref
        return lambda a: ref.deinterleave(a, fields)
    from repro.kernels import segment
    return lambda a: segment.deinterleave(a, fields,
                                          fused=impl == "pallas")


def _seg_int(txn: prg.Txn, specs: tuple):
    impl = txn.impl
    if impl == "ref":
        from repro.kernels import ref
        return lambda parts: ref.interleave(parts)
    from repro.kernels import segment
    return lambda parts: segment.interleave(parts, fused=impl == "pallas")


def _idx_gather(txn: prg.Txn, specs: tuple):
    spec = specs[0]
    if getattr(spec, "routing", None) is not None:
        # Static routing: the plan stage.  The layer take-masks are
        # computed ONCE here (concrete inputs -> concrete masks, even
        # under an outer jit trace) and the executor is memoized in
        # vx.PLANS under the spec key (routing included), so the payload
        # pays one static shift + one select per layer — on every impl,
        # since the masks are already compile-time constants.
        import numpy as np

        from repro.core import shiftnet
        shift = jnp.asarray(np.array(spec.routing[0], np.int32))
        valid = jnp.asarray(np.array(spec.routing[1], bool))
        masks, occ = shiftnet.layer_masks(shift, valid, toward_zero=True,
                                          lsb_first=True)

        def planned(buf):
            out = buf
            if masks.shape[0]:
                out = shiftnet.apply_layer_masks(out, masks, axis=-1,
                                                 toward_zero=True,
                                                 lsb_first=True)
            return jnp.where(occ, out, jnp.zeros_like(out))

        return planned
    if txn.impl == "ref":
        from repro.core import shiftnet

        def ref_idx(buf, shift, valid):
            res = shiftnet.gather_network(buf, shift, valid, axis=-1)
            return jnp.where(res.valid, res.payload,
                             jnp.zeros_like(res.payload))

        return ref_idx
    from repro.kernels import shift_gather as _sg
    return lambda buf, shift, valid: _sg.shift_gather(buf, shift, valid)


def _idx_scatter(txn: prg.Txn, specs: tuple):
    if txn.impl == "ref":
        from repro.core import shiftnet

        def ref_idx(values, shift, valid):
            res = shiftnet.scatter_network(values, shift, valid, axis=-1)
            return (jnp.where(res.valid, res.payload,
                              jnp.zeros_like(res.payload)),
                    jnp.broadcast_to(res.valid, values.shape))

        return ref_idx
    from repro.kernels import shift_scatter as _ss
    return lambda values, shift, valid: _ss.shift_scatter(values, shift,
                                                          valid)


def _compact_rows(txn: prg.Txn, specs: tuple):
    cap = specs[0].capacity

    if txn.impl == "ref":
        from repro.kernels import ref
        pack = ref.compact_rows
    else:
        from repro.kernels import moe_compact
        pack = moe_compact.compact_rows

    def fn(rows, mask):
        packed, valid = pack(rows, mask)
        if cap < packed.shape[0]:
            packed = jax.lax.slice_in_dim(packed, 0, cap, axis=0)
            valid = jax.lax.slice_in_dim(valid, 0, cap, axis=0)
        return packed, valid

    return fn


def _compact_ids(txn: prg.Txn, specs: tuple):
    cap = specs[0].capacity
    from repro.core import accessfuse
    return lambda mask: accessfuse.compact_indices(mask, cap)


def _lead_index(spec, pool, lead, batch_ndim: int) -> tuple:
    """Index prefix over a paged pool's lead dims for ``batch_ndim`` rows
    of page ids: with ``lead`` (a runtime index into the single lead axis
    of a stacked pool) that one entry, else every lead entry.  The lead
    axes are index coordinates, never part of the written window: a
    window that spans them makes XLA lay the whole pool out again around
    the scatter, while a window of one beat keeps the write in place."""
    pa = spec.pool_axis(pool.ndim)
    if lead is not None:
        if pa != 1:
            raise ValueError(f"lead= indexes one lead axis; the pool "
                             f"{pool.shape} has {pa}")
        return (lead,)
    return tuple(jnp.arange(n).reshape((1,) * d + (n,)
                                       + (1,) * (pa - 1 - d + batch_ndim))
                 for d, n in enumerate(pool.shape[:pa]))


def _paged_gather(txn: prg.Txn, specs: tuple):
    """Page-table gather: ``out[.., j, ..] = pool[.., t[j//ps], j%ps, ..]``.

    The table is a RUNTIME operand; only the geometry (page_size, pages,
    trail, dtype) is compiled state, so ONE cached executor serves every
    request and every decode step.  Page dispatch is one take at page
    granularity (each page is a contiguous beat — the access is already
    coalesced; the within-beat routing is the identity plan), entries
    ``< 0`` read as zeros.  Width-N fused transactions run on a stacked
    pool with ONE shared table — still a single gather (rank-agnostic:
    the page axis is found from the end).

    QUANTIZED specs (``scale_dtype`` set) take the per-page scale side
    tensor ``(*lead, P, *trail[:-1])`` as an extra operand and dequantize
    in the SAME program: the scale lookup is a one-hot contraction
    (iota + eq + dot — zero extra gather eqns, zero extra launches, and a
    ``-1`` table row one-hots to the zero vector), multiplied into the
    int page beats before the validity mask.  Masking AFTER the multiply
    matters for fp8: garbage on never-written pages can be NaN and
    ``0 * NaN`` would leak through a pre-mask.

    A runtime ``lead`` index reads one entry of a stacked pool's lead
    axis (one layer of a pool stack) without slicing the stack: the take
    indexes the stack directly, and the output has no lead dims.
    """
    spec = specs[0]
    ps, pages, trail = spec.page_size, spec.pages, spec.trail

    def take_pages(pool, table, lead):
        ids = jnp.maximum(table, 0)
        if lead is None:
            pa = spec.pool_axis(pool.ndim)
            return jnp.take(pool, ids, axis=pa), pa
        pre = _lead_index(spec, pool, lead, table.ndim)
        return pool.at[pre + (ids,)].get(mode="fill"), 0

    if spec.quantized:
        def qfn(pool, scales, table, lead=None):
            pa = spec.pool_axis(pool.ndim)
            if pool.shape[pa + 1] != ps:
                raise ValueError(
                    f"pool axis {pa + 1} has {pool.shape[pa + 1]} lanes, "
                    f"spec.page_size is {ps}")
            if table.shape[-1] != pages:
                raise ValueError(
                    f"table has {table.shape[-1]} pages, "
                    f"spec.pages is {pages}")
            P = pool.shape[pa]
            want = pool.shape[:pa] + (P,) + pool.shape[pa + 2:-1]
            if tuple(scales.shape) != want:
                raise ValueError(
                    f"scales shape {scales.shape} != {want} (per page, "
                    f"per trail dim except the last) for pool "
                    f"{pool.shape}")
            valid = table >= 0
            ints, pa = take_pages(pool, table, lead)
            if lead is not None:
                scales = scales[lead]
            # one-hot scale lookup: (*batch, pages, P) @ (P, *lead, *th)
            oh = (table[..., None] == jnp.arange(P)).astype(scales.dtype)
            s = jnp.tensordot(oh, jnp.moveaxis(scales, pa, 0), axes=1)
            bt = table.ndim
            if pa:   # lead dims back to the front
                s = jnp.moveaxis(s, tuple(range(bt, bt + pa)),
                                 tuple(range(pa)))
            s = jnp.expand_dims(s, pa + bt)     # the in-page axis
            if trail:
                s = s[..., None]                # shared last trail dim
            out = ints.astype(s.dtype) * s
            vshape = ((1,) * pa + table.shape + (1,) + (1,) * trail)
            out = jnp.where(valid.reshape(vshape), out,
                            jnp.zeros_like(out))
            shape = (out.shape[:pa + bt - 1] + (pages * ps,)
                     + out.shape[pa + bt + 1:])
            return out.reshape(shape)

        return qfn

    def fn(pool, table, lead=None):
        pa = spec.pool_axis(pool.ndim)
        if pool.shape[pa + 1] != ps:
            raise ValueError(
                f"pool axis {pa + 1} has {pool.shape[pa + 1]} lanes, "
                f"spec.page_size is {ps}")
        if table.shape[-1] != pages:
            raise ValueError(
                f"table has {table.shape[-1]} pages, spec.pages is {pages}")
        valid = table >= 0
        out, pa = take_pages(pool, table, lead)
        # out: (*lead, *batch, pages, ps, *trail); zero unallocated pages
        vshape = ((1,) * pa + table.shape + (1,) + (1,) * trail)
        out = jnp.where(valid.reshape(vshape), out, jnp.zeros_like(out))
        shape = (out.shape[:pa + table.ndim - 1] + (pages * ps,)
                 + out.shape[pa + table.ndim + 1:])
        return out.reshape(shape)

    return fn


def _paged_scatter(txn: prg.Txn, specs: tuple):
    """Decode append: one beat per table row, written through the page
    table at per-row position ``pos`` (``pos // ps`` picks the logical
    page, ``pos % ps`` the in-page offset).  Rows with ``pos < 0`` or an
    unallocated table entry are DROPPED (out-of-bounds scatter), so an
    inactive serving slot appends nothing.

    QUANTIZED specs append in three phases with a MONOTONE per-page
    scale (a page's scale only ever widens — shared CoW prefix pages are
    immutable, so a reader never races a rescale):

    1. scatter-max the beat's max-abs scale into the page's scale row,
    2. rescale the page's RESIDENT ints to the widened scale
       (``ratio = s_old / s_new <= 1``; a fresh page — ``s_old == 0`` —
       zeroes whatever garbage was resident).  Duplicate rows hitting
       the same physical page (chunked prefill writes up to ``ps`` beats
       into one page in a single scatter) write IDENTICAL content here:
       every read (s_old, s_new, the resident page) predates every
       write, so last-writer-wins is safe,
    3. quantize each beat at the final page scale and write it at its
       distinct ``(page, offset)`` — exactly the float arm's pattern.

    Returns ``(pool, scales)``.

    A pool with lead dims (a stack over layers) takes one beat per row
    broadcast over every lead entry, or one per lead entry and row
    (``values`` of ``(*lead, *batch, *trail)``: each layer's own beats in
    one scatter).  A runtime ``lead`` index writes one entry of a stacked
    pool's lead axis in place."""
    spec = specs[0]
    ps, trail = spec.page_size, spec.trail

    if spec.quantized:
        from repro.core import quant

        def qfn(pool, scales, values, table, pos, lead=None):
            if trail < 1:
                raise NotImplementedError(
                    "quantized paged scatter needs >= 1 trailing dim "
                    "(the max-abs scale reduces over the last)")
            P = pool.shape[spec.pool_axis(pool.ndim)]
            qm = quant.qmax(pool.dtype)
            pos = jnp.asarray(pos, jnp.int32)
            pre = _lead_index(spec, pool, lead, pos.ndim)
            oob = (pos < 0) | (pos >= spec.pages * ps)
            page = jnp.where(oob, 0, pos // ps)
            phys = jnp.take_along_axis(table, page[..., None],
                                       axis=-1)[..., 0]
            drop = oob | (phys < 0)
            physd = jnp.where(drop, P, phys)     # out of bounds -> dropped
            off = jnp.where(drop, ps, pos % ps)
            safe = jnp.clip(phys, 0, P - 1)      # reads for dropped rows
            # 1. widen: beat scale per (*lead, *batch, *trail[:-1])
            s_beat = jnp.max(jnp.abs(values), axis=-1) / qm
            s_old = scales[pre + (safe,)]
            scales = scales.at[pre + (physd,)].max(
                jnp.maximum(s_old, s_beat), mode="drop")
            s_fin = scales[pre + (safe,)]
            # 2. rescale resident ints to the widened scale
            ratio = jnp.where(s_fin > 0,
                              s_old / jnp.where(s_fin > 0, s_fin, 1.0),
                              1.0)
            rb = jnp.expand_dims(ratio, ratio.ndim - trail + 1)[..., None]
            pgs = pool[pre + (safe,)]
            pool = pool.at[pre + (physd,)].set(
                quant.requantize(pgs.astype(rb.dtype) * rb, pool.dtype),
                mode="drop")
            # 3. quantize the beat at the final page scale (safe divide:
            # an all-zero beat on a fresh page keeps scale 0 and writes
            # 0 — never NaN, fp8 has NaN encodings)
            qb = quant.quantize(values, s_fin[..., None], pool.dtype)
            pool = pool.at[pre + (physd, off)].set(qb, mode="drop")
            return pool, scales

        return qfn

    def fn(pool, values, table, pos, lead=None):
        pa = spec.pool_axis(pool.ndim)
        P = pool.shape[pa]
        pos = jnp.asarray(pos, jnp.int32)
        pre = _lead_index(spec, pool, lead, pos.ndim)
        oob = (pos < 0) | (pos >= spec.pages * ps)
        page = jnp.where(oob, 0, pos // ps)
        phys = jnp.take_along_axis(table, page[..., None], axis=-1)[..., 0]
        drop = oob | (phys < 0)
        phys = jnp.where(drop, P, phys)          # out of bounds -> dropped
        off = jnp.where(drop, ps, pos % ps)
        vals = values.astype(pool.dtype)
        if lead is None:     # per lead entry, or broadcast over them
            rows = values.shape[values.ndim - pos.ndim - trail:]
            vals = jnp.broadcast_to(vals, pool.shape[:pa] + rows)
        return pool.at[pre + (phys, off)].set(vals, mode="drop")

    return fn


def _compact_expand(txn: prg.Txn, specs: tuple):
    if txn.impl == "ref":
        from repro.kernels import ref
        return lambda packed, mask: ref.expand_rows(packed, mask)
    from repro.kernels import moe_compact
    return lambda packed, mask: moe_compact.expand_rows(packed, mask)


_BUILDERS = {
    "gather.plan": _gather_plan,
    "scatter.plan": _scatter_plan,
    "bank.gather": _bank_gather,
    "bank.scatter": _bank_scatter,
    "seg.deint": _seg_deint,
    "seg.int": _seg_int,
    "idx.gather": _idx_gather,
    "idx.scatter": _idx_scatter,
    "compact.rows": _compact_rows,
    "compact.ids": _compact_ids,
    "compact.expand": _compact_expand,
    "paged.gather": _paged_gather,
    "paged.scatter": _paged_scatter,
}


# ---------------------------------------------------------------------------
# Builders: sharded lowerings (shard-local plans under shard_map)
# ---------------------------------------------------------------------------

def _shard_index(shard: prg.Shard):
    """Flattened shard index, first mesh axis major (PartitionSpec order)."""
    idx = None
    for a in shard.axes:
        k = jax.lax.axis_index(a)
        idx = k if idx is None else idx * shard.mesh.shape[a] + k
    return idx


def _axis_spec(ndim: int, ax: int, shard: prg.Shard):
    from jax.sharding import PartitionSpec as P
    return P(*[shard.axes if i == ax else None for i in range(ndim)])


def _replicated_spec(ndim: int):
    from jax.sharding import PartitionSpec as P
    return P(*([None] * ndim))


def _shard_map(body, shard: prg.Shard, in_specs, out_specs):
    # check_vma off: bodies branch on lax.axis_index (device-varying by
    # construction) and merge with an explicit psum
    return jax.shard_map(body, mesh=shard.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _sub_strided(op: str, spec: Strided, impl: str, stride: int, cnt: int,
                 loff: int, nl: int):
    """The shard-local executor: the SAME pipeline, recursively, on the
    offset-rebased sub-spec (its program lands in vx.PLANS like any
    other).  ``stride`` is the Reverser-normalized (positive) stride."""
    import dataclasses
    sub = dataclasses.replace(spec, n=nl, stride=stride, offset=loff,
                              vl=cnt)
    return executor(lower(op, sub, impl), sub)


def _sharded_gather_plan(txn: prg.Txn, specs: tuple, shard: prg.Shard):
    from repro.core import shiftplan
    spec = specs[0]
    s, o, vl = spec.stride, spec.offset, spec.vl
    rev = s < 0
    if rev:                      # Reverser: plan on the flipped lane order
        o, s = o + (vl - 1) * s, -s
    R = shard.nshards
    nl = spec.n // R
    rows = shiftplan.shard_strided_rows(spec.n, s, o, vl, R)
    subs = [None if cnt == 0 else
            (lo, cnt, _sub_strided("gather.plan", spec, txn.impl,
                                   s, cnt, loff, nl))
            for lo, cnt, loff in rows]

    def mk(entry):
        if entry is None:
            return lambda x: jnp.zeros(x.shape[:-1] + (vl,), x.dtype)
        lo, cnt, sub = entry

        def br(x):
            dense = sub(x)
            pad = [(0, 0)] * (x.ndim - 1) + [(lo, vl - lo - cnt)]
            return jnp.pad(dense, pad)

        return br

    branches = [mk(e) for e in subs]

    def body(w):
        out = jax.lax.switch(_shard_index(shard), branches, w)
        # output lanes are disjoint across shards: psum == select
        return jax.lax.psum(out, shard.axes)

    def fn(w):
        ax = w.ndim - 1
        g = _shard_map(body, shard, (_axis_spec(w.ndim, ax, shard),),
                       _replicated_spec(w.ndim))
        out = g(w)
        return jnp.flip(out, -1) if rev else out

    return fn


def _sharded_scatter_plan(txn: prg.Txn, specs: tuple, shard: prg.Shard):
    from repro.core import shiftplan
    spec = specs[0]
    s, o = spec.stride, spec.offset
    vl = spec.vl
    rev = s < 0
    if rev:
        o, s = o + (vl - 1) * s, -s
    R = shard.nshards
    nl = spec.n // R
    rows = shiftplan.shard_strided_rows(spec.n, s, o, vl, R)
    subs = [None if cnt == 0 else
            (lo, cnt, _sub_strided("scatter.plan", spec, txn.impl,
                                   s, cnt, loff, nl))
            for lo, cnt, loff in rows]

    def mk(entry):
        if entry is None:
            return lambda x, v: x
        lo, cnt, sub = entry

        def br(x, v):
            vals = jax.lax.slice_in_dim(v, lo, lo + cnt, axis=-1)
            return sub(x, vals)

        return br

    branches = [mk(e) for e in subs]

    def body(w, v):
        return jax.lax.switch(_shard_index(shard), branches, w, v)

    def fn(w, v):
        ax = w.ndim - 1
        g = _shard_map(body, shard,
                       (_axis_spec(w.ndim, ax, shard),
                        _replicated_spec(v.ndim)),
                       _axis_spec(w.ndim, ax, shard))
        return g(w, jnp.flip(v, -1) if rev else v)

    return fn


def _sharded_seg_deint(txn: prg.Txn, specs: tuple, shard: prg.Shard):
    fields = specs[0].fields
    local = _seg_deint(txn, specs)

    def fn(aos):
        ax = aos.ndim + shard.axis
        if ax < 0 or ax == aos.ndim - 1:
            raise ValueError(f"shard axis {shard.axis} out of range for a "
                             f"rank-{aos.ndim} AoS operand")
        if aos.shape[ax] % shard.nshards:
            raise ValueError(
                f"operand dim {aos.shape[ax]} does not split into "
                f"{shard.nshards} shards")
        spec_in = _axis_spec(aos.ndim, ax, shard)
        g = _shard_map(lambda a: tuple(local(a)), shard, (spec_in,),
                       tuple(spec_in for _ in range(fields)))
        return list(g(aos))

    return fn


def _sharded_seg_int(txn: prg.Txn, specs: tuple, shard: prg.Shard):
    fields = specs[0].fields
    local = _seg_int(txn, specs)

    def fn(parts):
        parts = list(parts)
        ndim = parts[0].ndim
        ax = ndim + shard.axis
        if ax < 0 or ax == ndim - 1:
            raise ValueError(f"shard axis {shard.axis} out of range for a "
                             f"rank-{ndim} SoA operand")
        spec_in = _axis_spec(ndim, ax, shard)
        g = _shard_map(lambda *ps: local(list(ps)), shard,
                       tuple(spec_in for _ in range(fields)), spec_in)
        return g(*parts)

    return fn


def _sharded_paged_gather(txn: prg.Txn, specs: tuple, shard: prg.Shard):
    """Shard-local page gathers over a pool sharded on the page axis.

    Each shard owns a contiguous block of ``P // R`` physical pages; the
    (replicated) table is rebased into the local page-id space, entries
    owned elsewhere become ``-1`` (the replicated builder zeroes them),
    and ONE ``psum`` merges the disjoint per-shard contributions — every
    physical page has exactly one owner, so the psum is a select.  The
    sharded pool leaf is never sliced globally (the PR 4 invariant).

    Quantized pools shard the scale side tensor on the SAME page axis
    (scales are per physical page), so the inner quantized gather runs
    unchanged on the local page block with its local scales."""
    spec = specs[0]
    inner = _paged_gather(txn, specs)

    def fn(pool, *rest):
        scales, table = rest if spec.quantized else (None, rest[0])
        pa = spec.pool_axis(pool.ndim)
        P, R = pool.shape[pa], shard.nshards
        if P % R:
            raise ValueError(
                f"pool of {P} pages does not split into {R} equal shards")
        nl = P // R
        out_ndim = pool.ndim + table.ndim - 2

        def body(lp, tb):
            local = tb - _shard_index(shard) * nl
            owned = (tb >= 0) & (local >= 0) & (local < nl)
            out = inner(lp, jnp.where(owned, local, -1))
            return jax.lax.psum(out, shard.axes)

        def qbody(lp, ls, tb):
            local = tb - _shard_index(shard) * nl
            owned = (tb >= 0) & (local >= 0) & (local < nl)
            out = inner(lp, ls, jnp.where(owned, local, -1))
            return jax.lax.psum(out, shard.axes)

        pool_spec = _axis_spec(pool.ndim, pa, shard)
        if spec.quantized:
            g = _shard_map(qbody, shard,
                           (pool_spec, _axis_spec(scales.ndim, pa, shard),
                            _replicated_spec(table.ndim)),
                           _replicated_spec(out_ndim))
            return g(pool, scales, table)
        g = _shard_map(body, shard,
                       (pool_spec, _replicated_spec(table.ndim)),
                       _replicated_spec(out_ndim))
        return g(pool, table)

    return fn


_SHARDED_BUILDERS = {
    "gather.plan": _sharded_gather_plan,
    "scatter.plan": _sharded_scatter_plan,
    "seg.deint": _sharded_seg_deint,
    "seg.int": _sharded_seg_int,
    "paged.gather": _sharded_paged_gather,
}


def _build_sharded(txn: prg.Txn, specs: tuple, shard):
    if shard is None or shard.layout() != txn.layout:
        raise ValueError(
            f"program was lowered for layout {txn.layout} but executor "
            f"got {None if shard is None else shard.layout()}")
    if txn.op in ("gather.plan", "scatter.plan", "paged.gather") \
            and not txn.homogeneous:
        # a fused heterogeneous group reaches here through program.fuse
        # (per-access lower() only sees width 1): the sharded builder
        # compiles ONE rebased plan, which would silently apply spec 0's
        # pattern to every stacked row
        raise NotImplementedError(
            "heterogeneous fused strided transactions have no sharded "
            "lowering; gather replicated or split the group")
    return _SHARDED_BUILDERS[txn.op](txn, specs, shard)
