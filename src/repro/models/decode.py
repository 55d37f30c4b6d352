"""Single-token decode over the interleaved KV cache (serve_step body).

Cache layout (EARTH): each attention layer stores K and V interleaved along
features — appending a token is ONE dynamic_update_slice per layer (the
coalesced segment transaction), splitting at attention time is a FIELD=2
segment load. Sliding-window layers keep a ring buffer of exactly W beats
(RoPE is applied pre-cache, so scores are storage-order independent).

SSM / xLSTM blocks carry O(1) recurrent state — no KV growth, which is why
those archs run the long_500k cell.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import vx
from repro.kernels import kv_interleaved
from repro.models import attention, layers
from repro.models.ssm import init_mamba_cache, mamba_decode_step
from repro.models.moe import moe_ffn_local
from repro.models.transformer import ModelConfig, _ffn_apply
from repro.models.xlstm import (init_mlstm_state, init_slstm_state,
                                mlstm_decode_step, slstm_decode_step)
from repro.vx.cache import Tally

#: Pool leaves the paged programs write, counted by route when a program
#: is traced: ``in_place`` — only the new beats scatter into the pool
#: stack, which XLA then aliases to the donated input; ``scanned`` — the
#: stack rides the layer scan as an operand and a result, so each layer's
#: pool is sliced out and written back whole (the ``fuse=False`` oracle).
POOL_WRITES = Tally()


def cache_len_for_pos(cfg: ModelConfig, i: int, max_len: int) -> int:
    w = cfg.window_pattern[i]
    return min(w, max_len) if w is not None else max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    """Empty cache pytree; leaves stacked over superblocks (scan-ready)."""
    ns = cfg.n_superblocks
    blocks: dict[str, Any] = {}
    for i, kind in enumerate(cfg.block_pattern):
        if kind == "attn":
            sc = cache_len_for_pos(cfg, i, max_len)
            blocks[f"pos{i}"] = jnp.zeros(
                (ns, batch, sc, cfg.n_kv_heads, 2 * cfg.hd), dtype)
        elif kind == "mamba":
            c = init_mamba_cache(batch, cfg.mamba, dtype)
            blocks[f"pos{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (ns,) + a.shape), c)
        elif kind == "mlstm":
            s = init_mlstm_state(batch, cfg.xlstm)
            blocks[f"pos{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (ns,) + a.shape), s)
        elif kind == "slstm":
            s = init_slstm_state(batch, cfg.xlstm)
            blocks[f"pos{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (ns,) + a.shape), s)
    return {"len": jnp.zeros((), jnp.int32), "blocks": blocks}


def cache_from_prefill(cfg: ModelConfig, cache_states, seq_len: int,
                       max_len: int, dtype) -> dict:
    """Embed prefill-produced states into a max_len cache."""
    blocks = {}
    for i, kind in enumerate(cfg.block_pattern):
        st = cache_states[f"pos{i}"]
        if kind == "attn":
            sc = cache_len_for_pos(cfg, i, max_len)
            kv = st.astype(dtype)                      # (NS,B,S or W,K,2D)
            if kv.shape[2] < sc:
                kv = jnp.pad(kv, ((0, 0), (0, 0), (0, sc - kv.shape[2]),
                                  (0, 0), (0, 0)))
            elif kv.shape[2] > sc:
                kv = kv[:, :, :sc]
            blocks[f"pos{i}"] = kv
        else:
            blocks[f"pos{i}"] = st
    return {"len": jnp.asarray(seq_len, jnp.int32), "blocks": blocks}


# ---------------------------------------------------------------------------
# Paged KV cache: per-slot positions, a shared page pool per layer, one
# page table — memory scales with ACTIVE tokens, not slots x max_len.
# ---------------------------------------------------------------------------

def pages_per_seq(max_len: int, page_size: int) -> int:
    return -(-max_len // page_size)


def init_paged_cache(cfg: ModelConfig, slots: int, max_len: int,
                     page_size: int, dtype, *,
                     num_pages: int | None = None,
                     quantize: str | None = None) -> dict:
    """Paged cache pytree.

    Attention layers store a shared page POOL ``(NS, num_pages,
    page_size, K, 2D)`` instead of per-slot dense ``(NS, slots, max_len,
    K, 2D)`` buffers; ``table`` maps each slot's logical pages to
    physical pool pages (``-1`` = unallocated), ``pos`` is the per-slot
    position vector, and ``free``/``free_top`` form the device-side
    free-page stack (``free[:free_top]`` are free ids).  ``num_pages``
    defaults to full provisioning (``slots * pages_per_seq``); size it to
    the expected peak of active tokens to reclaim the memory.

    All attention layers page the FULL logical length (sliding windows
    become attention-time masks, not ring buffers — unattended pages of a
    finished window are reclaimable like any other).  Recurrent leaves
    (mamba/xlstm) stay per-slot O(1) state, as in :func:`init_cache`.

    ``ref`` is the device-side per-page REFERENCE COUNT: a physical page
    may back several slots' table entries at once (prefix sharing) plus
    external pins (the prefix trie, serve/prefix_cache.py).  Allocation
    sets ref=1; :func:`paged_adopt_prefix` / :func:`paged_addref` bump
    it; release/deref push a page back on the free stack only when its
    count hits zero.  The count lives on device because the decode step
    allocates inside jit — a host mirror would drift.

    ``quantize`` ("int8" / "fp8") switches the pool to the QUANTIZED
    layout: attention pool leaves store int8/fp8 and each gains a
    per-page-per-head float32 scale side tensor ``scl{i}`` of shape
    ``(NS, num_pages, K)`` living alongside ``pos{i}`` in ``blocks`` —
    scales ride the same scan/stack/fork plumbing as the pools, and the
    scale of physical page ``p`` travels with ``p`` through prefix
    adoption and CoW forks for free.  Recurrent leaves stay ``dtype``.

    Models with expert layers also carry ``moe``: int32 counters, summed
    over layers, of the units the paged prefill chunks and decode steps
    routed to the held experts (``routed``), dropped over capacity
    (``dropped``, 0 by construction) and computed per held expert
    (``per_expert``), accumulated on the device and read by the host only
    when asked (``Scheduler.stats``).
    """
    ns = cfg.n_superblocks
    n_seq = pages_per_seq(max_len, page_size)
    if num_pages is None:
        num_pages = slots * n_seq
    qdt = None
    if quantize is not None:
        from repro.core import quant
        qdt = quant.pool_dtype(quantize)
    blocks: dict[str, Any] = {}
    for i, kind in enumerate(cfg.block_pattern):
        if kind == "attn":
            blocks[f"pos{i}"] = jnp.zeros(
                (ns, num_pages, page_size, cfg.n_kv_heads, 2 * cfg.hd),
                qdt if qdt is not None else dtype)
            if qdt is not None:
                blocks[f"scl{i}"] = jnp.zeros(
                    (ns, num_pages, cfg.n_kv_heads), jnp.float32)
        elif kind == "mamba":
            c = init_mamba_cache(slots, cfg.mamba, dtype)
            blocks[f"pos{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (ns,) + a.shape), c)
        elif kind == "mlstm":
            s = init_mlstm_state(slots, cfg.xlstm)
            blocks[f"pos{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (ns,) + a.shape), s)
        elif kind == "slstm":
            s = init_slstm_state(slots, cfg.xlstm)
            blocks[f"pos{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (ns,) + a.shape), s)
    state = {
        "pos": jnp.zeros((slots,), jnp.int32),
        "table": jnp.full((slots, n_seq), -1, jnp.int32),
        # descending so pages allocate in 0, 1, 2, ... order
        "free": jnp.arange(num_pages - 1, -1, -1, dtype=jnp.int32),
        "free_top": jnp.asarray(num_pages, jnp.int32),
        "ref": jnp.zeros((num_pages,), jnp.int32),
        "blocks": blocks,
    }
    if any(cfg.moe_pattern):
        state["moe"] = {"routed": jnp.zeros((), jnp.int32),
                        "dropped": jnp.zeros((), jnp.int32),
                        "per_expert": jnp.zeros((cfg.moe.n_held,),
                                                jnp.int32)}
    return state


def _paged_geometry(cfg: ModelConfig, cache: dict):
    """(attn positions, page_size, pages_per_seq) from the cache leaves."""
    attn_pos = [i for i, k in enumerate(cfg.block_pattern) if k == "attn"]
    n_seq = cache["table"].shape[-1]
    ps = (cache["blocks"][f"pos{attn_pos[0]}"].shape[2] if attn_pos else 1)
    return attn_pos, ps, n_seq


def _pool_quantized(cache: dict, attn_pos) -> bool:
    """Quantized pool detection from the cache pytree itself (the scale
    side tensors are present) — every paged op switches on this, so a
    quantized cache flows through the scheduler unannotated."""
    return bool(attn_pos) and f"scl{attn_pos[0]}" in cache["blocks"]


def paged_invariants(cfg: ModelConfig, cache: dict, *,
                     external_ref=None) -> list[str]:
    """Audit the paged cache's STRUCTURAL invariants on a live pytree.

    Returns a list of human-readable violations (empty = healthy):

      * refcount conservation — every page's device refcount equals the
        number of table entries referencing it plus its EXTERNAL pins
        (``external_ref``: the prefix trie's per-page counts,
        serve/prefix_cache.py).  More table entries than refs is page
        ALIASING (a page shared without the books knowing would silently
        cross-contaminate attention and be reclaimed under a live slot);
      * free-stack consistency — ``free[:free_top]`` ids are in range and
        distinct; a page is on the free stack IFF its refcount is zero
        (an owned page on the stack is "both allocated and free"; a
        zero-ref page missing from it is leaked);
      * copy-on-write — a slot whose position ends MID-page must own its
        tail page exclusively (ref == 1): appends write that page in
        place, so a shared tail means a shared page is being written;
      * pos-vs-table occupancy — a slot at position ``p`` references at
        most ``ceil(p / page_size)`` pages, all at logical indices below
        that extent (starved slots may hold FEWER — local degradation —
        but never pages beyond their position);
      * bounds — ``0 <= free_top <= num_pages``, refcounts non-negative,
        positions within the logical capacity;
      * scale liveness (QUANTIZED pools) — every attention layer carries
        its ``scl{i}`` side tensor (all-or-none: a layer missing scales
        would gather garbage), scale geometry matches the pool, and
        every scale is finite and non-negative (the quantize safe-divide
        never writes NaN; a negative or non-finite scale means a page's
        beats can no longer be dequantized — the scale-tensor
        counterpart of refcount conservation).

    ONE device fetch (table / free / free_top / pos / ref — the small
    int state — plus the per-page scale tensors when quantized; the pool
    itself is never pulled), so the check is cheap enough to run
    per-step under the chaos harness.  The serve wrapper
    (serve/paged_cache.py ``check_invariants``) raises on violations.
    """
    import numpy as np
    attn_pos, ps, n_seq = _paged_geometry(cfg, cache)
    table, free, free_top, pos, ref = jax.device_get(
        (cache["table"], cache["free"], cache["free_top"], cache["pos"],
         cache["ref"]))
    table, free, pos, ref = (np.asarray(table), np.asarray(free),
                             np.asarray(pos), np.asarray(ref))
    free_top = int(free_top)
    num_pages = free.shape[0]
    out: list[str] = []
    if not attn_pos:
        return out                      # recurrent-only: no pool to audit
    if not (0 <= free_top <= num_pages):
        out.append(f"free_top={free_top} outside [0, {num_pages}]")
        return out                      # downstream slicing meaningless
    ext = (np.zeros(num_pages, np.int64) if external_ref is None
           else np.asarray(external_ref, np.int64))
    owned = table[table >= 0]
    if owned.size and (owned >= num_pages).any():
        out.append(f"table holds out-of-range page ids "
                   f"{sorted(set(owned[owned >= num_pages].tolist()))}")
        owned = owned[owned < num_pages]
    tc = np.bincount(owned, minlength=num_pages)     # table references
    if (ref < 0).any():
        out.append(f"page(s) {np.nonzero(ref < 0)[0].tolist()} hold "
                   f"negative refcounts (double release)")
    aliased = np.nonzero(tc > ref)[0]
    if aliased.size:
        out.append(f"page(s) {aliased.tolist()} aliased between slots "
                   f"(referenced {tc[aliased].tolist()} times, "
                   f"refcount {ref[aliased].tolist()})")
    bad_ref = np.nonzero((tc <= ref) & (ref != tc + ext))[0]
    if bad_ref.size:
        out.append(f"refcount conservation broken for page(s) "
                   f"{bad_ref.tolist()}: refcount "
                   f"{ref[bad_ref].tolist()} != table refs "
                   f"{tc[bad_ref].tolist()} + external pins "
                   f"{ext[bad_ref].tolist()}")
    stack = free[:free_top]
    uniq_f = np.unique(stack)
    if uniq_f.size != stack.size:
        out.append("free stack holds duplicate page ids")
    both = uniq_f[(tc[uniq_f] > 0) | (ref[uniq_f] > 0)] \
        if uniq_f.size else uniq_f
    if both.size:
        out.append(f"page(s) {both.tolist()} both allocated and free")
    live = np.zeros(num_pages, bool)
    live[uniq_f] = True
    leaked = np.nonzero(~live & (ref == 0) & (tc == 0))[0]
    if leaked.size:
        out.append(f"page(s) {leaked.tolist()} leaked (refcount zero "
                   f"but not on the free stack)")
    for s in range(table.shape[0]):
        p = int(pos[s])
        if p % ps != 0 and 0 <= p <= n_seq * ps:
            tail = int(table[s, p // ps])
            if tail >= 0 and ref[tail] > 1:
                out.append(f"slot {s}: partial tail page {tail} is "
                           f"SHARED (ref={int(ref[tail])}) — appends "
                           f"would write a shared page in place "
                           f"(missing copy-on-write fork)")
    for s in range(table.shape[0]):
        alloc = np.nonzero(table[s] >= 0)[0]
        p = int(pos[s])
        if not (0 <= p <= n_seq * ps):
            out.append(f"slot {s}: pos={p} outside [0, {n_seq * ps}]")
            continue
        extent = -(-p // ps)            # pages the position can reach
        if alloc.size > extent:
            out.append(f"slot {s}: owns {alloc.size} pages but "
                       f"pos={p} spans only {extent}")
        if alloc.size and alloc.max() >= extent:
            out.append(f"slot {s}: page at logical index "
                       f"{int(alloc.max())} beyond pos={p} extent "
                       f"{extent}")
    scl_layers = [i for i in attn_pos if f"scl{i}" in cache["blocks"]]
    if scl_layers:
        if len(scl_layers) != len(attn_pos):
            missing = sorted(set(attn_pos) - set(scl_layers))
            out.append(f"quantized pool missing scale tensor(s) for "
                       f"attention layer(s) {missing}")
        scls = jax.device_get([cache["blocks"][f"scl{i}"]
                               for i in scl_layers])
        for i, s in zip(scl_layers, scls):
            s = np.asarray(s)
            pool = cache["blocks"][f"pos{i}"]
            if s.shape[1] != num_pages or s.shape[0] != pool.shape[0] \
                    or s.shape[2] != pool.shape[3]:
                out.append(f"layer {i}: scale tensor shape {s.shape} "
                           f"does not match pool "
                           f"(NS={pool.shape[0]}, P={num_pages}, "
                           f"K={pool.shape[3]})")
                continue
            if not np.isfinite(s).all():
                bad = sorted(set(np.nonzero(~np.isfinite(s))[1].tolist()))
                out.append(f"layer {i}: non-finite scale on page(s) "
                           f"{bad} — beats there can never be "
                           f"dequantized")
            elif (s < 0).any():
                bad = sorted(set(np.nonzero(s < 0)[1].tolist()))
                out.append(f"layer {i}: negative scale on page(s) {bad}")
    return out


def _keep_active(new, old, active):
    """Per-slot state gate: inactive slots keep their old state."""
    def sel(n, o):
        m = active.reshape(active.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(sel, new, old)


def _deref_push(ref, free, free_top, ids):
    """Drop one reference from each page id in ``ids`` (pad with -1; ids
    must be distinct) and push pages whose count hits ZERO back on the
    free stack — shared pages (prefix runs still referenced by other
    slots or pinned by the trie) survive.  jit-safe."""
    ids = jnp.asarray(ids, jnp.int32)
    valid = ids >= 0
    safe = jnp.where(valid, ids, 0)
    ref = ref.at[safe].add(-valid.astype(jnp.int32))
    orphan = valid & (ref[safe] <= 0)
    rank = jnp.cumsum(orphan.astype(jnp.int32)) - orphan
    dst = jnp.where(orphan, free_top + rank, free.shape[0])
    free = free.at[dst].set(ids, mode="drop")
    free_top = free_top + jnp.sum(orphan.astype(jnp.int32))
    return ref, free, free_top


def paged_release_slot(cfg: ModelConfig, cache: dict, slot) -> dict:
    """Free a slot: drop one reference from each of its pages — ORPHANED
    pages (refcount zero) go back on the free stack, shared prefix pages
    survive for their other referents — then clear the slot's page table
    row and position and reset its recurrent state to init: a reused
    slot can never attend to (or carry) the previous occupant's state.
    Pool pages are NOT zeroed: a new occupant overwrites position ``p``
    before ``p`` ever becomes attendable (``eff_len`` masking), so stale
    beats are unreachable.  jit-safe (``slot`` may be traced)."""
    slot = jnp.asarray(slot, jnp.int32)
    table, free, free_top = cache["table"], cache["free"], cache["free_top"]
    row = jnp.take(table, slot, axis=0)                  # (pages,)
    ref, free, free_top = _deref_push(cache["ref"], free, free_top, row)
    table = table.at[slot].set(-1)
    pos = cache["pos"].at[slot].set(0)
    blocks = dict(cache["blocks"])
    for i, kind in enumerate(cfg.block_pattern):
        if kind == "attn":
            continue
        if kind == "mamba":
            ini = init_mamba_cache(1, cfg.mamba, jnp.float32)
        elif kind == "mlstm":
            ini = init_mlstm_state(1, cfg.xlstm)
        else:
            ini = init_slstm_state(1, cfg.xlstm)
        leaf = blocks[f"pos{i}"]
        blocks[f"pos{i}"] = jax.tree.map(
            lambda c, s: c.at[:, slot].set(
                jnp.broadcast_to(s[0], c.shape[2:]).astype(c.dtype)),
            leaf, ini)
    return dict(cache, pos=pos, table=table, free=free, free_top=free_top,
                ref=ref, blocks=blocks)


def paged_insert_prefill(cfg: ModelConfig, cache: dict, slot,
                         cache_states, length, state_len: int) -> dict:
    """Embed B=1 prefill states into a slot's pages.

    ``state_len`` (static) is the sequence extent the prefill ran on —
    any length; attention beats are zero-padded here to whole pages
    (the padded tail is masked by ``eff_len`` until the decode loop
    overwrites it in place).  ``length`` is the true prompt length and
    may be TRACED, so ONE jit entry serves every prompt of the same
    ``state_len``.  NOTE on padding the PREFILL itself (running it on
    more tokens than the prompt): that is only sound for windowless
    attention-only stacks — a ring-trimmed window leaf is cut at the
    padded length (real in-window beats are lost) and recurrent state
    absorbs the pad tokens irreversibly; the serving scheduler therefore
    pads the prefill only when every block is windowless attention.
    Allocates ``ceil(state_len / page_size)`` pages off the free stack.
    jit-safe (``slot``/``length`` may be traced)."""
    attn_pos, ps, n_seq = _paged_geometry(cfg, cache)
    n_pg = -(-state_len // ps)
    sp = n_pg * ps
    if n_pg > n_seq:
        raise ValueError(f"state_len={state_len} needs {n_pg} pages, the "
                         f"table holds {n_seq}")
    slot = jnp.asarray(slot, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    free, free_top = cache["free"], cache["free_top"]
    # exhaustion degrades locally (like the decode-step allocator): pages
    # beyond the free count stay -1 in the table and their beats are
    # dropped — never an aliased page.  serve/paged_cache.py refuses the
    # insert host-side before it comes to that.
    k = jnp.arange(n_pg)
    have = k < free_top
    newp = jnp.where(have, free[jnp.clip(free_top - 1 - k, 0,
                                         free.shape[0] - 1)], -1)
    free_top = free_top - jnp.sum(have.astype(jnp.int32))
    table = cache["table"].at[slot, :n_pg].set(newp)
    pos = cache["pos"].at[slot].set(length)
    scatter_ids = jnp.where(have, newp, free.shape[0])
    ref = cache["ref"].at[scatter_ids].add(1, mode="drop")
    quantized = _pool_quantized(cache, attn_pos)
    blocks = dict(cache["blocks"])
    for i, kind in enumerate(cfg.block_pattern):
        st = cache_states[f"pos{i}"]
        leaf = blocks[f"pos{i}"]
        if kind == "attn":
            # quantized pools keep the prefill states float here and
            # quantize per page below (casting to the int leaf dtype
            # would truncate)
            kv = st.astype(jnp.float32 if quantized else leaf.dtype)
            w = cfg.window_pattern[i]
            if w is not None and kv.shape[2] < state_len:
                # prefill ring-trimmed the window at state_len: un-roll
                # to natural order and park at positions
                # [state_len - W, state_len)
                W = kv.shape[2]
                nat = jnp.roll(kv, -(state_len % W), axis=2)
                full = jnp.zeros(kv.shape[:2] + (sp,) + kv.shape[3:],
                                 kv.dtype)
                kv = full.at[:, :, state_len - W:state_len].set(nat)
            elif kv.shape[2] != state_len:
                raise ValueError(
                    f"prefill states carry {kv.shape[2]} beats; expected "
                    f"state_len={state_len}")
            if kv.shape[2] < sp:       # zero-pad to whole pages
                kv = jnp.pad(kv, ((0, 0), (0, 0), (0, sp - kv.shape[2]),
                                  (0, 0), (0, 0)))
            beats = kv[:, 0].reshape(kv.shape[0], n_pg, ps, *kv.shape[3:])
            if quantized:
                # per-(superblock, page, head) max-abs scale over the
                # in-page and feature axes, then quantize the beats
                from repro.core import quant
                s = quant.scale_for(beats, leaf.dtype, axis=(2, 4))
                beats = quant.quantize(beats, s[:, :, None, :, None],
                                       leaf.dtype)
                blocks[f"scl{i}"] = blocks[f"scl{i}"].at[
                    :, scatter_ids].set(s, mode="drop")
            blocks[f"pos{i}"] = leaf.at[:, scatter_ids].set(beats,
                                                            mode="drop")
        else:
            blocks[f"pos{i}"] = jax.tree.map(
                lambda c, s: c.at[:, slot].set(s[:, 0].astype(c.dtype)),
                leaf, st)
    return dict(cache, pos=pos, table=table, free=free, free_top=free_top,
                ref=ref, blocks=blocks)


def paged_adopt_prefix(cfg: ModelConfig, cache: dict, slot,
                       page_ids) -> dict:
    """Point a freshly-admitted slot's page table at SHARED prefix pages.

    ``page_ids`` is ``(pages_per_seq,)`` int32 — the physical page run
    backing the slot's leading logical pages, padded with -1.  Each
    adopted page gains one reference and NO device data moves: the page
    gather already reads through the table, so a shared page costs
    nothing beyond the table row write.  The slot's position is set to
    ``(#adopted) * page_size`` (whole pages only — a partial tail is
    forked separately, :func:`paged_fork_page`).  The slot must be empty
    (released) before adoption.  jit-safe (``slot``/``page_ids`` may be
    traced)."""
    attn_pos, ps, n_seq = _paged_geometry(cfg, cache)
    slot = jnp.asarray(slot, jnp.int32)
    ids = jnp.asarray(page_ids, jnp.int32)
    valid = ids >= 0
    table = cache["table"].at[slot].set(jnp.where(valid, ids, -1))
    drop = cache["free"].shape[0]
    ref = cache["ref"].at[jnp.where(valid, ids, drop)].add(1, mode="drop")
    pos = cache["pos"].at[slot].set(
        jnp.sum(valid.astype(jnp.int32)) * ps)
    return dict(cache, pos=pos, table=table, ref=ref)


def paged_addref(cfg: ModelConfig, cache: dict, page_ids) -> dict:
    """Add one EXTERNAL reference (a prefix-trie pin) to each page id in
    ``page_ids`` (padded with -1).  Pinned pages survive the release of
    every slot that references them — the trie keeps published prefixes
    resident for future borrowers until it evicts them
    (:func:`paged_deref_pages`).  jit-safe."""
    ids = jnp.asarray(page_ids, jnp.int32)
    valid = ids >= 0
    drop = cache["free"].shape[0]
    ref = cache["ref"].at[jnp.where(valid, ids, drop)].add(1, mode="drop")
    out = dict(cache)
    out["ref"] = ref
    return out


def paged_deref_pages(cfg: ModelConfig, cache: dict, page_ids) -> dict:
    """Drop one reference from each page id in ``page_ids`` (padded with
    -1) — the trie-eviction counterpart of :func:`paged_addref`.  Pages
    whose count hits zero go back on the free stack.  jit-safe."""
    ref, free, free_top = _deref_push(cache["ref"], cache["free"],
                                      cache["free_top"], page_ids)
    out = dict(cache)
    out.update(ref=ref, free=free, free_top=free_top)
    return out


def paged_fork_page(cfg: ModelConfig, cache: dict, slot, logical_idx,
                    src, *, deref_src: bool = False, pos_to=None) -> dict:
    """Copy-on-write fork: pop a fresh page off the free stack, copy the
    SOURCE page's pool beats into it across every attention layer, and
    point ``table[slot, logical_idx]`` at the copy (refcount 1).

    Used at admission when a prompt's tail matches only PART of a trie
    page (the slot adopts the shared whole-page run, then forks the
    donor's next page to continue writing mid-page), and on any append
    that would land on a page with refcount > 1.  ``deref_src=True``
    additionally drops one reference on ``src`` — the append-time case,
    where the slot previously referenced the shared page; admission-time
    tail forks (the slot never referenced the donor page) leave the
    source's count alone.  ``pos_to`` (traced), when given, sets the
    slot's position (admission forks park it at ``k * page_size + m``).
    Callers check ``free_pages() >= 1`` host-side; an exhausted stack
    degrades locally (the entry stays -1, the copy drops).  jit-safe."""
    attn_pos, ps, n_seq = _paged_geometry(cfg, cache)
    slot = jnp.asarray(slot, jnp.int32)
    logical_idx = jnp.asarray(logical_idx, jnp.int32)
    src = jnp.asarray(src, jnp.int32)
    free, free_top, ref = cache["free"], cache["free_top"], cache["ref"]
    drop = free.shape[0]
    have = free_top > 0
    newp = jnp.where(have, free[jnp.clip(free_top - 1, 0, drop - 1)],
                     -1)
    free_top = free_top - have.astype(jnp.int32)
    table = cache["table"].at[slot, logical_idx].set(newp)
    ref = ref.at[jnp.where(have, newp, drop)].add(1, mode="drop")
    dst = jnp.where(have & (src >= 0), newp, drop)
    rst = jnp.where(have, newp, drop)
    srcc = jnp.clip(src, 0, drop - 1)
    blocks = dict(cache["blocks"])
    for i, kind in enumerate(cfg.block_pattern):
        if kind != "attn":
            continue
        leaf = blocks[f"pos{i}"]                  # (NS, P, ps, K, 2D)
        beat = jax.lax.dynamic_index_in_dim(leaf, srcc, axis=1)
        blocks[f"pos{i}"] = leaf.at[:, dst].set(beat[:, 0], mode="drop")
        if f"scl{i}" in blocks:
            # the scale forks WITH the page, BEFORE any write lands on
            # the copy (the monotone-widen rule then evolves the fork's
            # scale independently of the immutable shared source).  A
            # sourceless fork (src < 0: fresh empty page) resets the
            # scale instead — stale garbage would poison the first
            # widen's s_old.  Reset-then-copy: dst drops when src < 0,
            # so the reset survives exactly then.
            scl = blocks[f"scl{i}"]               # (NS, P, K)
            scl = scl.at[:, rst].set(0.0, mode="drop")
            srow = jax.lax.dynamic_index_in_dim(scl, srcc, axis=1)
            blocks[f"scl{i}"] = scl.at[:, dst].set(srow[:, 0],
                                                   mode="drop")
    if deref_src:
        ref, free, free_top = _deref_push(ref, free, free_top,
                                          jnp.where(src >= 0, src,
                                                    -1)[None])
    pos = cache["pos"]
    if pos_to is not None:
        pos = pos.at[slot].set(jnp.asarray(pos_to, jnp.int32))
    return dict(cache, pos=pos, table=table, free=free, free_top=free_top,
                ref=ref, blocks=blocks)


def _moe_ffn(p, x, cfg: ModelConfig, valid):
    """The paged serve path's expert FFN, residual included: x (..., d),
    ``valid`` of ``x.shape[:-1]``, False for tokens that route nowhere
    (pad tokens, idle slots).  One device, so the capacity holds every
    unit and nothing drops.  Returns (x, the layer's units)."""
    h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
    m = p["moe"]
    y, _, units = moe_ffn_local(
        m["router"], m["wg"], m["wu"], m["wo"], h.reshape(-1, cfg.d_model),
        cfg.moe, model_axis=None, data_axes=(), n_shards=1,
        valid=valid.reshape(-1))
    return x + y.reshape(x.shape), units


def _counted(cache: dict, units: dict) -> dict:
    """``cache`` with the expert layers' ``units`` (per position, stacked
    over superblocks) added to its ``moe`` counters."""
    if not units:
        return cache
    moe = dict(cache["moe"])
    for u in units.values():
        for name, v in u.items():
            moe[name] = moe[name] + jnp.sum(v, axis=0)
    return dict(cache, moe=moe)


def _pool_keys(cache: dict, attn_pos) -> list[str]:
    """The ``blocks`` keys of the page pool: each attention layer's
    ``pos{i}`` and, for a quantized pool, its ``scl{i}`` scales."""
    return [f"{kind}{i}" for i in attn_pos for kind in ("pos", "scl")
            if f"{kind}{i}" in cache["blocks"]]


def _write_beats(spec, blocks: dict, beats: dict, table, pos,
                 policy) -> dict:
    """``blocks`` with every attention layer's new beats written into its
    pool stack in place: ``beats[f"pos{i}"]`` is ``(NS, R, K, 2D)``, and
    row ``r`` of superblock ``l`` goes to position ``pos[r]`` through
    ``table[r]`` in that superblock's pages.  One page scatter per pool
    leaf, the superblock a leading coordinate; a quantized pool widens
    its ``scl{i}`` scales in the same scatter."""
    out = dict(blocks)
    for key, b in beats.items():
        scl = "scl" + key[3:]
        new = vx.scatter(spec, blocks[key], b, table=table, pos=pos,
                         scales=blocks.get(scl), policy=policy)
        if scl in blocks:
            new, out[scl] = new
        out[key] = new
    return out


def _scanned_blocks(cache: dict, blocks: dict, attn_pos,
                    fuse: bool) -> dict:
    """The cache leaves that ride the layer scan as operands and results:
    every leaf on the ``fuse=False`` oracle route, the recurrent ones only
    when the pool is written in place.  Counts the pool's leaves in
    :data:`POOL_WRITES` by route."""
    keys = _pool_keys(cache, attn_pos)
    for _ in keys:
        POOL_WRITES.add("in_place" if fuse else "scanned")
    if not fuse:
        return blocks
    return {k: v for k, v in blocks.items() if k not in keys}


def _scan_layers(cfg: ModelConfig, sb_step, carry, xs):
    """``sb_step`` over the superblocks — one scan, or unrolled when
    ``cfg.scan_layers`` is off: ``(carry, per-superblock results
    stacked)``."""
    if cfg.scan_layers:
        return jax.lax.scan(sb_step, carry, xs)
    outs = []
    for sbi in range(cfg.n_superblocks):
        carry, out = sb_step(carry, jax.tree.map(lambda a: a[sbi], xs))
        outs.append(out)
    return carry, jax.tree.map(lambda *ys: jnp.stack(ys), *outs)


def paged_prefill_chunk(params, cache: dict, tokens: jax.Array,
                        cfg: ModelConfig, ctx, *, slot, count,
                        fuse: bool | None = None) -> dict:
    """Prefill up to ``tokens.shape[0]`` prompt tokens into ONE slot,
    starting at the slot's current position (mid-page starts — e.g.
    after a copy-on-write tail fork — are fine).

    ``tokens`` has a FIXED width (the scheduler uses one page), so every
    chunk of a serving process shares ONE jit trace and one set of
    compiled access plans; ``count`` (traced) is the number of leading
    tokens that are real.  Pad tokens append nothing (dropped scatter
    rows), never touch recurrent state, route to no expert, and their
    garbage activations feed no output.

    This is the CANONICAL prefill path for the serving scheduler: every
    page's contents become a deterministic function of (the prefix
    tokens, this one trace), which is what makes prefix pages sharable
    bit-exactly — a borrower adopting a donor's pages reads exactly the
    bits it would have computed itself.  Missing pages in the touched
    range are allocated off the free stack (refcount 1), the chunk's KV
    beats scatter through the page table, C-query causal attention runs
    over the slot's gathered pages (sliding windows mask at attention
    time, as in the paged decode step), and recurrent blocks advance
    token-by-token under a scan.  No logits are computed: the scheduler
    feeds the LAST prompt token through the decode step to produce the
    first sampled token (the PR 6 replay cursor), so chunks cover
    ``prompt[:-1]`` only.  Returns the updated cache.

    Where the pool is written: attention must read the chunk's own beats
    through the pool (quantized on write for int8/fp8 pools), so the pool
    stack rides the layer scan's CARRY.  Each layer scatters its beats
    into the carried stack at its own superblock (``lead=``) and gathers
    the slot's row from the same stack; nothing pool-sized is sliced or
    written back, and XLA aliases the carry to the donated input.
    ``fuse=False`` (default ``cfg.step_fusion``) is the test oracle: the
    stack rides the scan as an operand and a result, one layer's pool at
    a time."""
    from repro.models.transformer import cast_params
    params = cast_params(params, cfg)
    if cfg.encoder is not None:
        raise NotImplementedError("paged serving covers decoder-only "
                                  "models; use encdec.decode_step")
    fuse = cfg.step_fusion if fuse is None else fuse
    pol = cfg.vx_policy
    C = tokens.shape[0]
    slot = jnp.asarray(slot, jnp.int32)
    count = jnp.asarray(count, jnp.int32)
    attn_pos, ps, n_seq = _paged_geometry(cfg, cache)
    table, free, free_top = cache["table"], cache["free"], cache["free_top"]
    ref, pos = cache["ref"], cache["pos"]
    start = jnp.take(pos, slot)
    offs = jnp.arange(C)
    tpos = start + offs                          # (C,) token positions
    real = offs < count
    seq = n_seq * ps if attn_pos else (1 << 30)

    spec = None
    quantized = _pool_quantized(cache, attn_pos)
    blocks_in = cache["blocks"]
    if attn_pos:
        # allocate every missing page the chunk touches (same rank-pop as
        # the decode step; exhaustion degrades locally — entries stay -1
        # and the touched beats drop, never an aliased page)
        row = jnp.take(table, slot, axis=0)      # (n_seq,)
        idx = jnp.arange(n_seq)
        lastp = (start + jnp.maximum(count, 1) - 1) // ps
        neednew = (idx >= start // ps) & (idx <= lastp) & (row < 0)
        rank = jnp.cumsum(neednew.astype(jnp.int32)) - neednew
        have = neednew & (rank < free_top)
        newp = free[jnp.clip(free_top - 1 - rank, 0, free.shape[0] - 1)]
        row = jnp.where(have, newp, row)
        table = table.at[slot].set(row)
        free_top = free_top - jnp.sum(have.astype(jnp.int32))
        ref = ref.at[jnp.where(have, newp, free.shape[0])].add(
            1, mode="drop")
        if quantized:
            # fresh pages start at scale 0: stale garbage scales would
            # poison the monotone widen's s_old (and the rescale would
            # never zero the resident garbage ints)
            rst = jnp.where(have, newp, free.shape[0])
            blocks_in = dict(blocks_in)
            for i in attn_pos:
                blocks_in[f"scl{i}"] = blocks_in[f"scl{i}"].at[
                    :, rst].set(0.0, mode="drop")
        table_c = jnp.broadcast_to(row, (C, n_seq))
        wpos = jnp.where(real & (tpos < seq), tpos, -1)
        spec = vx.Paged(page_size=ps, pages=n_seq, trail=2)
    scanned = _scanned_blocks(cache, blocks_in, attn_pos, fuse)
    # in place, the pool stack rides the scan's carry
    pools = {k: v for k, v in blocks_in.items() if k not in scanned}

    x = layers.embed(tokens, params["embed"]).astype(cfg.cdtype)[None]

    def _tok_scan(step_fn, state0, h, keep_dtype):
        """Advance per-slot recurrent state over the chunk's tokens; pad
        tokens are gated out of both the carry and the output."""
        def tok(st, inp):
            ht, on = inp                         # ht (1, d), on scalar
            y, st2 = step_fn(ht, st)
            st2 = _keep_active(st2, st, on[None])
            return st2, jnp.where(on, y, 0.0)
        st, ys = jax.lax.scan(tok, state0,
                              (jnp.swapaxes(h, 0, 1), real))
        return st, jnp.swapaxes(ys, 0, 1).astype(keep_dtype)

    def _slot_state(sb_state):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0),
            sb_state)

    def _put_slot(sb_state, new1):
        return jax.tree.map(
            lambda full, s1: jax.lax.dynamic_update_slice_in_dim(
                full, s1.astype(full.dtype), slot, axis=0),
            sb_state, new1)

    def sb_step(carry, inp):
        x, pools = carry
        sb_p, sb_c, layer = inp
        pools = dict(pools)
        new_c, units = {}, {}
        for i, kind in enumerate(cfg.block_pattern):
            p = sb_p[f"pos{i}"]
            if kind == "attn":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                q, k, v, kv = attention.qkv_project(
                    p["attn"], h, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                    tpos[None], cfg.rope_theta, policy=pol)
                # in place: this superblock of the carried stack;
                # oracle: the layer's own pool (P, ps, K, 2D)
                src, dst, lead = ((pools, pools, layer) if fuse
                                  else (sb_c, new_c, None))
                # quantize-on-write (scale widens monotonically), then
                # the attention read dequantizes the slot's whole prefix
                # — including the beats just written — in the same
                # one-program gather
                pool = vx.scatter(spec, src[f"pos{i}"], kv[0], table=table_c,
                                  pos=wpos, scales=src.get(f"scl{i}"),
                                  lead=lead, policy=pol)
                if quantized:
                    pool, dst[f"scl{i}"] = pool
                full = vx.gather(spec, pool, table=row[None],
                                 scales=dst.get(f"scl{i}"), lead=lead,
                                 policy=pol)
                dst[f"pos{i}"] = pool
                k_all, v_all = vx.transpose(
                    vx.Segment(n=full.shape[-1], fields=2), full,
                    policy=pol)
                out = attention.chunk_attention(
                    q, k_all, v_all, tpos, window=cfg.window_pattern[i])
                x = x + (out.reshape(1, C, cfg.n_heads * cfg.hd)
                         @ p["attn"]["wo"]).astype(x.dtype)
            elif kind == "mamba":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                pm = dict(p["mamba"])
                pm["in_proj"] = pm["in_proj"].reshape(cfg.d_model,
                                                      2 * cfg.mamba.ed)
                st, y = _tok_scan(
                    lambda ht, st: mamba_decode_step(pm, ht, st,
                                                     cfg.mamba),
                    _slot_state(sb_c[f"pos{i}"]), h, x.dtype)
                x = x + y
                new_c[f"pos{i}"] = _put_slot(sb_c[f"pos{i}"], st)
            elif kind == "mlstm":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                px = dict(p["xl"])
                px["up"] = px["up"].reshape(cfg.d_model,
                                            2 * cfg.xlstm.m_inner)
                st, y = _tok_scan(
                    lambda ht, st: mlstm_decode_step(px, ht, st,
                                                     cfg.xlstm),
                    _slot_state(sb_c[f"pos{i}"]), h, x.dtype)
                x = x + y
                new_c[f"pos{i}"] = _put_slot(sb_c[f"pos{i}"], st)
            elif kind == "slstm":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                st, y = _tok_scan(
                    lambda ht, st: slstm_decode_step(p["slstm"], ht, st,
                                                     cfg.xlstm),
                    _slot_state(sb_c[f"pos{i}"]), h, x.dtype)
                x = x + y
                new_c[f"pos{i}"] = _put_slot(sb_c[f"pos{i}"], st)
            if cfg.pos_has_ffn(i) and cfg.moe_pattern[i]:
                x, units[f"pos{i}"] = _moe_ffn(p, x, cfg, real[None])
            elif cfg.pos_has_ffn(i):
                x, _ = _ffn_apply(p, x, cfg, ctx, i, policy=pol)
        return (x, pools), (new_c, units)

    (_, pools), (new_blocks, units) = _scan_layers(
        cfg, sb_step, (x, pools),
        (params["blocks"], scanned, jnp.arange(cfg.n_superblocks)))
    blocks = dict(blocks_in, **new_blocks, **pools)
    new_pos = pos.at[slot].set(jnp.minimum(start + count, seq))
    return _counted(dict(cache, pos=new_pos, table=table, free=free,
                         free_top=free_top, ref=ref, blocks=blocks),
                    units)


def paged_truncate(cfg: ModelConfig, cache: dict, new_pos) -> dict:
    """Truncate every slot's position DOWN to ``new_pos`` (B,) and free
    the pages past the new extent — the draft-cache sync of speculative
    decoding (the draft ran ahead on tokens the target then rejected).

    Pages at logical indices >= ceil(new_pos / page_size) lose one
    reference and return to the free stack when their count hits zero.
    The truncated range must not be prefix-shared ACROSS slots (ids fed
    to the free-stack push must be distinct) — the serving scheduler
    only truncates the draft pool, which never runs the prefix trie.
    Beats left in the surviving tail page beyond ``new_pos`` are
    unreachable (``eff_len`` masking) and overwritten in place by the
    next append.  Recurrent leaves are untouched (the draft stack is
    validated attention-only by the scheduler).  jit-safe."""
    attn_pos, ps, n_seq = _paged_geometry(cfg, cache)
    table, free, free_top = cache["table"], cache["free"], cache["free_top"]
    pos = cache["pos"]
    new_pos = jnp.minimum(jnp.clip(jnp.asarray(new_pos, jnp.int32),
                                   0, n_seq * ps), pos)
    ext = (new_pos + ps - 1) // ps                   # surviving extent
    idx = jnp.arange(n_seq)[None, :]
    roll = (table >= 0) & (idx >= ext[:, None])
    ids = jnp.where(roll, table, -1).reshape(-1)
    ref, free, free_top = _deref_push(cache["ref"], free, free_top, ids)
    table = jnp.where(roll, -1, table)
    return dict(cache, pos=new_pos, table=table, free=free,
                free_top=free_top, ref=ref)


def paged_verify_step(params, cache: dict, tokens: jax.Array,
                      cfg: ModelConfig, ctx, *, n_draft, active=None,
                      fuse: bool | None = None, pool_shard=None):
    """Speculative K-token verify over the paged cache.

    ``tokens`` is ``(B, K)`` int32 — column 0 is each slot's CURRENT
    token (the last committed sample), columns 1..K-1 the draft model's
    proposals.  ``n_draft`` (B,) in [1, K] is the number of REAL columns
    per slot (traced: one jit trace serves every mixture of per-request
    speculation widths, so `vx.PLANS` sees one spec).  Returns
    ``(logits (B, K, V), out_tok (B, K), commit (B,), new_cache)``:
    ``out_tok[:, j] = argmax(logits[:, j])`` and ``commit`` is the
    greedy accept count — 1 (the token column 0 would have produced
    anyway) plus the number of LEADING drafts that match the argmax of
    the previous column.  The committed stream ``out_tok[b, :commit]``
    is exactly the token stream the non-speculative greedy oracle
    produces (K=1 degenerates to :func:`paged_decode_step` plus argmax).

    Access shape: the K draft positions stack along the BEAT axis of the
    existing ``vx.Paged`` programs — the append flattens ``(B, K)`` rows
    into ``(B*K,)`` scatter rows through the SAME paged-scatter arm the
    chunked prefill uses (table rows repeated K times), and the read is
    the SAME one fused page-gather + one fused FIELD=2 split as the
    single-token step: one spec, one gather eqn, one pinned launch,
    regardless of K.  Attention gathers PRE-append pages and the K fresh
    beats are inserted as floats (a scatter, not a gather), so fused /
    per-access / quantized arms all see bit-identical attention inputs,
    and float pools match the single-token oracle bitwise.

    Where the pool is written: as in :func:`paged_decode_step`, the fused
    loop never reads the pool; each layer returns its ``B*K`` fresh
    beats, and after the loop one page scatter per pool leaf writes them
    all into the donated stack in place.  The ``fuse=False`` oracle scans
    the stack one layer's pool at a time.

    Rejection rolls back through the page table ONLY: pages allocated
    this step at logical indices past the accept extent are guaranteed
    refcount-1 (a slot's pre-step pages never extend past its position
    — the invariant audit's occupancy rule), so the rollback clears
    their table entries and pushes them straight back on the free stack
    — no pool copy, no CoW trigger, and the refcount-conservation audit
    holds at every step boundary.  Beats written past the accept point
    (including the surviving tail page's rejected beats) become
    unreachable stale storage, overwritten before they are ever
    attendable.  QUANTIZED pools: rejected beats may have widened a
    surviving tail page's scale (the widen is monotone and never
    narrows), so speculation on int8/fp8 pools is bounded-error rather
    than bit-exact — same bound class as the quantized pool itself.

    Recurrent blocks (mamba/xlstm) advance token-by-token under a scan
    with every intermediate state collected; the state at the accept
    point is selected post-hoc (a K-way where, no gather), so rejected
    tokens never contaminate the carry.
    """
    from repro.models.transformer import cast_params
    params = cast_params(params, cfg)
    if cfg.encoder is not None:
        raise NotImplementedError("paged serving covers decoder-only "
                                  "models; use encdec.decode_step")
    fuse = cfg.step_fusion if fuse is None else fuse
    pol = cfg.vx_policy
    B, K = tokens.shape
    pos = cache["pos"]
    if active is None:
        active = jnp.ones((B,), bool)
    else:
        active = jnp.asarray(active, bool)
    n_draft = jnp.clip(jnp.asarray(n_draft, jnp.int32), 1, K)
    attn_pos, ps, n_seq = _paged_geometry(cfg, cache)
    table, free, free_top = cache["table"], cache["free"], cache["free_top"]
    ref = cache["ref"]
    quantized = _pool_quantized(cache, attn_pos)
    blocks_in = cache["blocks"]
    seq = n_seq * ps if attn_pos else (1 << 30)
    num_pages = free.shape[0] if attn_pos else 0

    offs = jnp.arange(K)[None, :]
    tpos = pos[:, None] + offs                       # (B, K) positions
    valid = active[:, None] & (offs < n_draft[:, None])
    have = newp_grid = None
    spec = None
    if attn_pos:
        # batched multi-page allocation: exactly the pages the oracle
        # would allocate crossing boundaries in [pos, pos + n_draft - 1]
        # (fresh pages start at a boundary >= pos, so a degraded missing
        # mid-page tail is never re-allocated — oracle behavior).
        # Exhaustion degrades locally, as in the single-token step.
        idx = jnp.arange(n_seq)[None, :]
        startp = (pos + ps - 1) // ps
        lastp = (pos + n_draft - 1) // ps
        need = (active[:, None] & (idx >= startp[:, None])
                & (idx <= lastp[:, None]) & (table < 0))
        flat = need.reshape(-1)
        rank = jnp.cumsum(flat.astype(jnp.int32)) - flat
        have_f = flat & (rank < free_top)
        newp_f = free[jnp.clip(free_top - 1 - rank, 0, num_pages - 1)]
        have = have_f.reshape(B, n_seq)
        newp_grid = jnp.where(have, newp_f.reshape(B, n_seq), -1)
        table = jnp.where(have, newp_grid, table)
        free_top = free_top - jnp.sum(have_f.astype(jnp.int32))
        ref = ref.at[jnp.where(have_f, newp_f, num_pages)].add(
            1, mode="drop")
        if quantized:
            rst = jnp.where(have_f, newp_f, num_pages)
            blocks_in = dict(blocks_in)
            for i in attn_pos:
                blocks_in[f"scl{i}"] = blocks_in[f"scl{i}"].at[
                    :, rst].set(0.0, mode="drop")
        spec = vx.Paged(page_size=ps, pages=n_seq, trail=2)
    # K-beat append plumbing: (B*K,) scatter rows through the table rows
    # repeated K times — the chunked-prefill shape, one program per layer
    wpos = jnp.where(valid & (tpos < seq), tpos, -1)       # (B, K)
    wpos_flat = wpos.reshape(-1)
    table_flat = jnp.repeat(table, K, axis=0) if attn_pos else None
    qpos = jnp.where(valid, tpos, -1)                      # pad queries
    b_idx = jnp.arange(B)[:, None]
    wp_ins = jnp.where(valid & (tpos < seq), tpos, seq)    # drop pads

    x = layers.embed(tokens, params["embed"]).astype(cfg.cdtype)  # (B,K,d)

    pre_split: dict[str, Any] = {}
    if fuse and attn_pos:
        gathered = kv_interleaved.gather_paged_kv(
            [blocks_in[f"pos{i}"] for i in attn_pos], table, ps,
            policy=pol, shard=pool_shard,
            scales=([blocks_in[f"scl{i}"] for i in attn_pos]
                    if quantized else None))
        splits = kv_interleaved.split_kv_step(gathered, policy=pol)
        pre_split = {f"pos{i}": splits[a] for a, i in enumerate(attn_pos)}
    scanned = _scanned_blocks(cache, blocks_in, attn_pos, fuse)
    beat_pol = (pol.for_elems(B * K * cfg.n_kv_heads * 2 * cfg.hd)
                if fuse else pol)
    ffn_pol = pol.for_elems(B * K * 2 * cfg.d_ff) if fuse else pol

    def _tok_scan_b(step_fn, state0, h, keep_dtype):
        """Advance B slots' recurrent state over the K tokens, collecting
        every intermediate state for the post-hoc accept-point select."""
        def tok(st, inp):
            ht, on = inp                             # ht (B, d), on (B,)
            y, st2 = step_fn(ht, st)
            st2 = _keep_active(st2, st, on)
            return st2, (st2, jnp.where(on[:, None], y, 0.0))
        _, (sts, ys) = jax.lax.scan(
            tok, state0, (jnp.swapaxes(h, 0, 1), jnp.swapaxes(valid, 0, 1)))
        return sts, jnp.swapaxes(ys, 0, 1).astype(keep_dtype)

    def sb_step(x, inp):
        sb_p, sb_c, sb_pre = inp
        new_c, units = {}, {}
        for i, kind in enumerate(cfg.block_pattern):
            p = sb_p[f"pos{i}"]
            if kind == "attn":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                q, k, v, kv = attention.qkv_project(
                    p["attn"], h, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                    tpos, cfg.rope_theta, policy=beat_pol)
                kv_flat = kv.reshape(B * K, cfg.n_kv_heads, 2 * cfg.hd)
                if fuse:
                    # the fresh beats, written into the stack after the
                    # loop
                    new_c[f"pos{i}"] = kv_flat
                    k_pre, v_pre = sb_pre[f"pos{i}"]
                else:
                    pool = sb_c[f"pos{i}"]           # (P, ps, Kh, 2D)
                    scl = sb_c.get(f"scl{i}")
                    # per-access oracle reads PRE-append too (then the
                    # fresh-beat insert below), so every arm sees
                    # bit-identical attention inputs
                    full = vx.gather(spec, pool, table=table, scales=scl,
                                     policy=pol, shard=pool_shard)
                    k_pre, v_pre = vx.transpose(
                        vx.Segment(n=full.shape[-1], fields=2), full,
                        policy=pol)
                    pool = vx.scatter(spec, pool, kv_flat, table=table_flat,
                                      pos=wpos_flat, scales=scl, policy=pol)
                    if quantized:
                        pool, new_c[f"scl{i}"] = pool
                    new_c[f"pos{i}"] = pool
                # insert ALL K fresh beats as floats (a scatter eqn —
                # the gather gate stays at one); rows past n_draft drop
                k_all = k_pre.at[b_idx, wp_ins].set(
                    k.astype(k_pre.dtype), mode="drop")
                v_all = v_pre.at[b_idx, wp_ins].set(
                    v.astype(v_pre.dtype), mode="drop")
                out = attention.chunk_attention(
                    q, k_all, v_all, qpos, window=cfg.window_pattern[i])
                x = x + (out.reshape(B, K, cfg.n_heads * cfg.hd)
                         @ p["attn"]["wo"]).astype(x.dtype)
            elif kind == "mamba":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                pm = dict(p["mamba"])
                pm["in_proj"] = pm["in_proj"].reshape(cfg.d_model,
                                                      2 * cfg.mamba.ed)
                sts, y = _tok_scan_b(
                    lambda ht, st: mamba_decode_step(pm, ht, st,
                                                     cfg.mamba),
                    sb_c[f"pos{i}"], h, x.dtype)
                x = x + y
                new_c[f"pos{i}"] = sts
            elif kind == "mlstm":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                px = dict(p["xl"])
                px["up"] = px["up"].reshape(cfg.d_model,
                                            2 * cfg.xlstm.m_inner)
                sts, y = _tok_scan_b(
                    lambda ht, st: mlstm_decode_step(px, ht, st,
                                                     cfg.xlstm),
                    sb_c[f"pos{i}"], h, x.dtype)
                x = x + y
                new_c[f"pos{i}"] = sts
            elif kind == "slstm":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                sts, y = _tok_scan_b(
                    lambda ht, st: slstm_decode_step(p["slstm"], ht, st,
                                                     cfg.xlstm),
                    sb_c[f"pos{i}"], h, x.dtype)
                x = x + y
                new_c[f"pos{i}"] = sts
            if cfg.pos_has_ffn(i) and cfg.moe_pattern[i]:
                x, units[f"pos{i}"] = _moe_ffn(p, x, cfg, valid)
            elif cfg.pos_has_ffn(i):
                x, _ = _ffn_apply(p, x, cfg, ctx, i, policy=ffn_pol)
        return x, (new_c, units)

    x, (new_blocks, units) = _scan_layers(
        cfg, sb_step, x, (params["blocks"], scanned, pre_split))
    beats = ({f"pos{i}": new_blocks.pop(f"pos{i}") for i in attn_pos}
             if fuse else {})
    blocks = _write_beats(spec, dict(blocks_in, **new_blocks), beats,
                          table_flat, wpos_flat, pol)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = layers.unembed(x, head.astype(cfg.cdtype))    # (B, K, V)

    # greedy accept recurrence: column j's argmax is the oracle token at
    # position pos + j given columns 0..j were fed correctly; commit =
    # 1 + (# leading drafts matching the previous column's argmax)
    out_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if K > 1:
        match = ((tokens[:, 1:] == out_tok[:, :-1])
                 & (jnp.arange(1, K)[None, :] < n_draft[:, None]))
        m = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    else:
        m = jnp.zeros((B,), jnp.int32)
    commit = jnp.where(active, 1 + m, 0)
    new_pos = jnp.where(active, jnp.minimum(pos + commit, seq), pos)

    # accept-point select for recurrent leaves: state after `commit`
    # tokens is stacked index commit-1 (inactive slots carried their old
    # state through the gated scan, so any index reads it back)
    ci = jnp.clip(commit, 1, K) - 1

    def _sel(a):                                     # (NS, K, B, ...)
        out = a[:, 0]
        for kk in range(1, K):
            mkk = (ci == kk).reshape((1, -1) + (1,) * (out.ndim - 2))
            out = jnp.where(mkk, a[:, kk], out)
        return out

    for i, kind in enumerate(cfg.block_pattern):
        if kind != "attn":
            blocks[f"pos{i}"] = jax.tree.map(_sel, new_blocks[f"pos{i}"])

    if attn_pos:
        # rollback via the page table only: pages allocated THIS step at
        # logical indices past the accept extent are refcount-1 by
        # construction — clear the entries and push them back
        ext = (new_pos + ps - 1) // ps
        roll = have & (jnp.arange(n_seq)[None, :] >= ext[:, None])
        ids = jnp.where(roll, newp_grid, -1).reshape(-1)
        ref, free, free_top = _deref_push(ref, free, free_top, ids)
        table = jnp.where(roll, -1, table)

    return logits, out_tok, commit, _counted(dict(
        cache, pos=new_pos, table=table, free=free, free_top=free_top,
        ref=ref, blocks=blocks), units)


def paged_decode_step(params, cache: dict, token: jax.Array,
                      cfg: ModelConfig, ctx, *, active=None,
                      fuse: bool | None = None,
                      pool_shard=None) -> tuple[jax.Array, dict]:
    """One decode step over the paged cache.  token: (B,) int32 with B =
    slots.  Returns (logits (B, V), updated cache).

    Differences from :func:`decode_step` (which remains the dense-cache
    oracle): positions are PER-SLOT (``cache["pos"]``), the step takes an
    ``active`` mask (idle slots append nothing, advance nothing and route
    to no expert — the scheduler's active-set batching), appends allocate
    a page off the device free stack when a slot crosses a page boundary,
    and attention reads go through ``vx.Paged`` — with ``fuse=True`` ALL
    layers' page gathers run as ONE fused page-granular program (the table
    encodes the heterogeneous per-slot lengths; the compiled program is
    keyed only by page geometry) followed by the usual ONE fused FIELD=2
    split.
    ``fuse=False`` is the per-access paged oracle.  Sliding-window layers
    mask at attention time instead of ring-overwriting.

    Where the pool is written: attention reads the pre-append pages from
    that one gather plus the fresh beat, so the layer loop never reads the
    pool.  Each layer returns its fresh beats ``(B, K, 2D)`` as a small
    scan result, and after the loop ONE page scatter per pool leaf writes
    every superblock's beats into the donated stack in place, the
    superblock a leading scatter coordinate (``-1`` and out-of-range rows
    drop; a pool sharded on its page axis stays sharded).  The
    ``fuse=False`` oracle instead scans the stack, scattering and reading
    back one layer's pool at a time.

    ``pool_shard`` (a ``vx.Shard`` on the pool page axis, ``axis=-4``)
    lowers every page gather shard-locally — the pool, sharded over the
    mesh on its page axis, is never sliced globally (the PR 4 invariant
    applied to the serving pool).

    QUANTIZED pools (``scl{i}`` side tensors present, see
    :func:`init_paged_cache`) dequantize inside the same fused gather
    program, quantize the appended beat on write (page scale widens
    monotonically), and attention always reads the pre-append pages
    plus the fresh FLOAT beat — fused and per-access paths stay
    bit-identical, and the beat is only quantized for the NEXT step's
    read.
    """
    from repro.models.transformer import cast_params
    params = cast_params(params, cfg)
    if cfg.encoder is not None:
        raise NotImplementedError("paged serving covers decoder-only "
                                  "models; use encdec.decode_step")
    fuse = cfg.step_fusion if fuse is None else fuse
    pol = cfg.vx_policy
    B = token.shape[0]
    pos = cache["pos"]
    if active is None:
        active = jnp.ones((B,), bool)
    else:
        active = jnp.asarray(active, bool)
    attn_pos, ps, n_seq = _paged_geometry(cfg, cache)
    table, free, free_top = cache["table"], cache["free"], cache["free_top"]
    ref = cache["ref"]
    quantized = _pool_quantized(cache, attn_pos)
    blocks_in = cache["blocks"]
    # logical capacity; recurrent-only stacks carry O(1) state, no cap
    seq = n_seq * ps if attn_pos else (1 << 30)

    if attn_pos:
        # allocate on page-boundary crossing — one shared table update for
        # every layer (all layers append in lockstep).  An exhausted free
        # stack degrades LOCALLY: slots whose pop rank exceeds the free
        # count get no page (table entry stays -1, their appends drop and
        # their reads return zeros) — never an aliased page shared with a
        # live slot, and free_top never goes negative.
        need = active & (pos % ps == 0) & (pos // ps < n_seq)
        rank = jnp.cumsum(need.astype(jnp.int32)) - need
        need = need & (rank < free_top)
        newp = free[jnp.clip(free_top - 1 - rank, 0, free.shape[0] - 1)]
        hit = need[:, None] & (jnp.arange(n_seq)[None, :]
                               == (pos // ps)[:, None])
        table = jnp.where(hit, newp[:, None], table)
        free_top = free_top - jnp.sum(need.astype(jnp.int32))
        ref = ref.at[jnp.where(need, newp, free.shape[0])].add(
            1, mode="drop")
        if quantized:
            # freshly allocated pages start at scale 0 (see the chunk
            # allocator): the widen-on-append then zeroes resident
            # garbage and the first beat sets the true scale
            rst = jnp.where(need, newp, free.shape[0])
            blocks_in = dict(blocks_in)
            for i in attn_pos:
                blocks_in[f"scl{i}"] = blocks_in[f"scl{i}"].at[
                    :, rst].set(0.0, mode="drop")
    # idle slots and full sequences append nothing (dropped scatter rows)
    write_pos = jnp.where(active & (pos < seq), pos, -1)
    spec = (vx.Paged(page_size=ps, pages=n_seq, trail=2)
            if attn_pos else None)

    x = layers.embed(token, params["embed"]).astype(cfg.cdtype)

    pre_split: dict[str, Any] = {}
    if fuse and attn_pos:
        # ONE fused page gather for all layers' pools (stacked over
        # superblocks AND over layers), then ONE fused FIELD=2 split.
        # Quantized pools stack their scale tensors the same way — the
        # dequant rides the same single program (zero extra launches).
        gathered = kv_interleaved.gather_paged_kv(
            [blocks_in[f"pos{i}"] for i in attn_pos], table, ps,
            policy=pol, shard=pool_shard,
            scales=([blocks_in[f"scl{i}"] for i in attn_pos]
                    if quantized else None))
        splits = kv_interleaved.split_kv_step(gathered, policy=pol)
        pre_split = {f"pos{i}": splits[a] for a, i in enumerate(attn_pos)}
    scanned = _scanned_blocks(cache, blocks_in, attn_pos, fuse)
    beat_pol = (pol.for_elems(B * cfg.n_kv_heads * 2 * cfg.hd)
                if fuse else pol)
    ffn_pol = pol.for_elems(B * 2 * cfg.d_ff) if fuse else pol
    eff = (pos + active.astype(jnp.int32))[:, None]      # (B, 1) per slot

    def sb_step(x, inp):
        sb_p, sb_c, sb_pre = inp
        new_c, units = {}, {}
        for i, kind in enumerate(cfg.block_pattern):
            p = sb_p[f"pos{i}"]
            if kind == "attn":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                q, k, v, kv = attention.qkv_project(
                    p["attn"], h[:, None], cfg.n_heads, cfg.n_kv_heads,
                    cfg.hd, pos[:, None], cfg.rope_theta, policy=beat_pol)
                if fuse:
                    # the fresh beat, written into the stack after the loop
                    new_c[f"pos{i}"] = kv[:, 0]
                    pre = sb_pre[f"pos{i}"]
                else:
                    pool = sb_c[f"pos{i}"]             # (P, ps, K, 2D)
                    scl = sb_c.get(f"scl{i}")
                    if quantized:
                        # per-access quantized arm reads PRE-append (like
                        # the fused pre-gather) and inserts the fresh
                        # FLOAT beat below — attention then sees
                        # bit-identical inputs on both paths (the
                        # appended beat is only quantized for the NEXT
                        # step's read, exactly as in the fused arm)
                        full = vx.gather(spec, pool, table=table,
                                         scales=scl, policy=pol,
                                         shard=pool_shard)
                        pre = vx.transpose(
                            vx.Segment(n=full.shape[-1], fields=2), full,
                            policy=pol)
                    pool = vx.scatter(spec, pool, kv[:, 0], table=table,
                                      pos=write_pos, scales=scl, policy=pol)
                    if quantized:
                        pool, new_c[f"scl{i}"] = pool
                    new_c[f"pos{i}"] = pool
                if fuse or quantized:
                    k_pre, v_pre = pre
                    ins = (active[:, None]
                           & (jnp.arange(seq)[None, :] == pos[:, None]))
                    ins = ins[:, :, None, None]
                    # k/v are (B, 1, K, D): broadcast over the seq axis
                    k_all = jnp.where(ins, k.astype(k_pre.dtype), k_pre)
                    v_all = jnp.where(ins, v.astype(v_pre.dtype), v_pre)
                else:
                    full = vx.gather(spec, pool, table=table, policy=pol,
                                     shard=pool_shard)   # (B, S, K, 2D)
                    k_all, v_all = vx.transpose(
                        vx.Segment(n=full.shape[-1], fields=2), full,
                        policy=pol)
                out = attention.decode_attention(
                    q[:, 0], k_all, v_all, eff,
                    window=cfg.window_pattern[i])
                x = x + (out.reshape(B, cfg.n_heads * cfg.hd)
                         @ p["attn"]["wo"]).astype(x.dtype)
            elif kind == "mamba":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                pm = dict(p["mamba"])
                pm["in_proj"] = pm["in_proj"].reshape(cfg.d_model,
                                                      2 * cfg.mamba.ed)
                y, st = mamba_decode_step(pm, h, sb_c[f"pos{i}"], cfg.mamba)
                x = x + jnp.where(active[:, None], y, 0)
                new_c[f"pos{i}"] = _keep_active(st, sb_c[f"pos{i}"], active)
            elif kind == "mlstm":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                px = dict(p["xl"])
                px["up"] = px["up"].reshape(cfg.d_model,
                                            2 * cfg.xlstm.m_inner)
                y, st = mlstm_decode_step(px, h, sb_c[f"pos{i}"], cfg.xlstm)
                x = x + jnp.where(active[:, None], y, 0)
                new_c[f"pos{i}"] = _keep_active(st, sb_c[f"pos{i}"], active)
            elif kind == "slstm":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                y, st = slstm_decode_step(p["slstm"], h, sb_c[f"pos{i}"],
                                          cfg.xlstm)
                x = x + jnp.where(active[:, None], y, 0)
                new_c[f"pos{i}"] = _keep_active(st, sb_c[f"pos{i}"], active)
            if cfg.pos_has_ffn(i) and cfg.moe_pattern[i]:
                x, units[f"pos{i}"] = _moe_ffn(p, x, cfg, active)
            elif cfg.pos_has_ffn(i):
                x2, _ = _ffn_apply(p, x[:, None], cfg, ctx, i,
                                   policy=ffn_pol)
                x = x2[:, 0]
        return x, (new_c, units)

    x, (new_blocks, units) = _scan_layers(
        cfg, sb_step, x, (params["blocks"], scanned, pre_split))
    beats = ({f"pos{i}": new_blocks.pop(f"pos{i}") for i in attn_pos}
             if fuse else {})
    blocks = _write_beats(spec, dict(blocks_in, **new_blocks), beats,
                          table, write_pos, pol)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = layers.unembed(x, head.astype(cfg.cdtype))
    new_pos = pos + (active & (pos < seq)).astype(jnp.int32)
    return logits, _counted(dict(cache, pos=new_pos, table=table, free=free,
                                 free_top=free_top, ref=ref,
                                 blocks=blocks), units)


def decode_step(params, cache: dict, token: jax.Array, cfg: ModelConfig,
                ctx, *, fuse: bool | None = None,
                kv_shard=None) -> tuple[jax.Array, dict]:
    """token: (B,) int32. Returns (logits (B, V), updated cache).

    ``fuse`` (default cfg.step_fusion) enables whole-step access fusion:
    the attention-time cache splits of EVERY layer — the step's dominant
    shift-routed traffic — are hoisted to the top of the step (they read
    the pre-append cache, which depends on nothing computed this step) and
    merged into ONE fused FIELD=2 segment load: one kernel launch and one
    mask operand per decode step instead of one per layer.  The current
    token's (k, v) is then written into the pre-split arrays at its slot
    (two one-beat updates), which is bit-exact with splitting the
    post-append cache because the segment op is a pure lane permutation.
    Single-token reorganizations (QKV beat pack/split, GLU field split)
    are inlined on the XLA path by the scheduler's launch policy.
    ``fuse=False`` keeps the per-access path (the equivalence oracle).

    ``kv_shard`` (a ``vx.Shard`` with ``axis=-3``, the cache sequence
    axis) marks the KV leaves as sequence-sharded: the fused split then
    lowers SHARD-LOCALLY under ``shard_map`` (repro.vx.lower), which is
    what lets long-context seq-parallel serving keep step fusion — the
    global split of a sharded leaf that used to force SPMD
    rematerialization is gone.  Leaves whose sequence extent does not
    divide across the shards (short sliding windows) fall back to the
    replicated lowering, each group still one fused launch.
    """
    from repro.models.transformer import cast_params
    params = cast_params(params, cfg)
    if cfg.encoder is not None:
        from repro.models import encdec
        return encdec.decode_step(params, cache, token, cfg, ctx)
    fuse = cfg.step_fusion if fuse is None else fuse
    pol = cfg.vx_policy
    B = token.shape[0]
    pos = cache["len"]
    x = layers.embed(token, params["embed"]).astype(cfg.cdtype)

    attn_pos = [i for i, k in enumerate(cfg.block_pattern) if k == "attn"]
    pre_split: dict[str, Any] = {}
    if fuse and attn_pos:
        # One fused split for all layers: leaves are stacked over
        # superblocks ((NS, B, Sc, K, 2D)), so this single call covers the
        # full depth; same-shape positions share one launch.  Sharded and
        # replicated leaves lower separately (the scheduler groups by
        # placement as well as shape).
        leaves = {i: cache["blocks"][f"pos{i}"] for i in attn_pos}
        sharded = [i for i, leaf in leaves.items()
                   if kv_shard is not None
                   and kv_shard.divides(leaf.shape[-3])]
        local = [i for i in attn_pos if i not in sharded]
        splits: dict[int, Any] = {}
        if sharded:
            outs = kv_interleaved.split_kv_step(
                [leaves[i] for i in sharded], policy=pol, shard=kv_shard)
            splits.update(dict(zip(sharded, outs)))
        if local:
            outs = kv_interleaved.split_kv_step(
                [leaves[i] for i in local], policy=pol)
            splits.update(dict(zip(local, outs)))
        pre_split = {f"pos{i}": splits[i] for i in attn_pos}
    # single-token reorganizations (QKV beat split, GLU field split) ride
    # the XLA path below the policy's fusion threshold during fused decode
    beat_pol = (pol.for_elems(B * cfg.n_kv_heads * 2 * cfg.hd)
                if fuse else pol)
    ffn_pol = pol.for_elems(B * 2 * cfg.d_ff) if fuse else pol

    def sb_step(x, inp):
        sb_p, sb_c, sb_pre = inp
        new_c = {}
        for i, kind in enumerate(cfg.block_pattern):
            p = sb_p[f"pos{i}"]
            if kind == "attn":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                positions = jnp.broadcast_to(pos, (B, 1))
                q, k, v, kv = attention.qkv_project(
                    p["attn"], h[:, None], cfg.n_heads, cfg.n_kv_heads,
                    cfg.hd, positions, cfg.rope_theta, policy=beat_pol)
                kvc = sb_c[f"pos{i}"]                      # (B, Sc, K, 2D)
                sc = kvc.shape[1]
                slot = jax.lax.rem(pos, sc)
                kvc = jax.lax.dynamic_update_slice_in_dim(
                    kvc, kv.astype(kvc.dtype), slot, axis=1)
                if fuse:
                    k_pre, v_pre = sb_pre[f"pos{i}"]
                    k_all = jax.lax.dynamic_update_slice_in_dim(
                        k_pre, k.astype(kvc.dtype), slot, axis=1)
                    v_all = jax.lax.dynamic_update_slice_in_dim(
                        v_pre, v.astype(kvc.dtype), slot, axis=1)
                else:
                    k_all, v_all = vx.transpose(
                        vx.Segment(n=kvc.shape[-1], fields=2), kvc,
                        policy=pol)
                eff_len = jnp.minimum(pos + 1, sc)
                out = attention.decode_attention(
                    q[:, 0], k_all, v_all, eff_len, window=None)
                x = x + (out.reshape(B, cfg.n_heads * cfg.hd)
                         @ p["attn"]["wo"]).astype(x.dtype)
                new_c[f"pos{i}"] = kvc
            elif kind == "mamba":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                pm = dict(p["mamba"])
                pm["in_proj"] = pm["in_proj"].reshape(cfg.d_model,
                                                      2 * cfg.mamba.ed)
                y, st = mamba_decode_step(pm, h, sb_c[f"pos{i}"], cfg.mamba)
                x = x + y
                new_c[f"pos{i}"] = st
            elif kind == "mlstm":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                px = dict(p["xl"])
                px["up"] = px["up"].reshape(cfg.d_model,
                                            2 * cfg.xlstm.m_inner)
                y, st = mlstm_decode_step(px, h, sb_c[f"pos{i}"], cfg.xlstm)
                x = x + y
                new_c[f"pos{i}"] = st
            elif kind == "slstm":
                h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
                y, st = slstm_decode_step(p["slstm"], h, sb_c[f"pos{i}"],
                                          cfg.xlstm)
                x = x + y
                new_c[f"pos{i}"] = st
            if cfg.pos_has_ffn(i):
                x2, _ = _ffn_apply(p, x[:, None], cfg, ctx, i,
                                   policy=ffn_pol)
                x = x2[:, 0]
        return x, new_c

    if cfg.scan_layers:
        x, new_blocks = jax.lax.scan(
            sb_step, x, (params["blocks"], cache["blocks"], pre_split))
    else:
        outs = []
        for sbi in range(cfg.n_superblocks):
            sb = jax.tree.map(lambda a: a[sbi], params["blocks"])
            cb = jax.tree.map(lambda a: a[sbi], cache["blocks"])
            pb = jax.tree.map(lambda a: a[sbi], pre_split)
            x, nb = sb_step(x, (sb, cb, pb))
            outs.append(nb)
        new_blocks = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = layers.unembed(x, head.astype(cfg.cdtype))
    return logits, {"len": pos + 1, "blocks": new_blocks}
