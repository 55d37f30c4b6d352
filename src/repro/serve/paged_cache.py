"""Paged KV-cache state for serving: page pool + page table + free stack.

The device state itself lives in the cache pytree built by
``models/decode.init_paged_cache`` (pos / table / free / free_top /
blocks); this module wraps it with the HOST bookkeeping a scheduler needs
— capacity checks before admission, page accounting, jit'd release /
prefill-insert entry points — so `serve/scheduler.py` never touches the
pytree layout directly.

Memory model: attention layers share one pool of ``num_pages`` physical
pages per layer, so cache memory scales with ACTIVE tokens
(``pages_in_use * page_bytes``), not with ``slots * max_len`` the way the
dense fixed-slot cache does.  ``num_pages`` defaults to full
provisioning (every slot can reach ``max_len``).  Sizing it smaller
OVERCOMMITS the pool: the scheduler's admission check
(`serve/scheduler.py`) reserves pages for every live request's current
tokens plus headroom (not the max_len worst case), so long-running
decodes can still exhaust the stack mid-flight — when they do, the
decode step degrades locally (the starved slot's appends drop, no page
is ever aliased between slots) and the condition is observable as
``free_pages() == 0``; ``insert_prefill`` refuses outright rather than
starve a prompt.

PR 8 adds PREFIX SHARING on top of the same pool: pages carry a device
refcount (``state["ref"]``), a slot can ADOPT another request's pages
(``adopt_prefix`` points its table row at a shared run — the fused
gather already reads through the table, so sharing costs zero new
device work), a partial tail page is FORKED copy-on-write
(``fork_page``) before the borrower ever writes into it, and the
radix trie (serve/prefix_cache.py) holds an external +1 pin per
published page (``addref`` / ``deref_pages``).  ``check_invariants``
audits refcount conservation — every page's refcount equals the number
of table entries referencing it plus the trie pin — via the
``external_ref`` provider hook the scheduler installs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels._common import pytree_nbytes
from repro.models import decode as dec
from repro.models.transformer import ModelConfig


class InvariantViolation(AssertionError):
    """A structural page-pool invariant broke on a live engine (page
    aliasing, free-stack corruption, pos/table divergence).  This is a
    state-management bug, never load: admission pressure degrades
    locally by design and must NOT trip this."""


class PagedCache:
    """Page pool + page-table state for a fixed-slot serving loop.

    ``debug_invariants=True`` audits the pool's structural invariants
    (:func:`repro.models.decode.paged_invariants`) after every mutation
    — one small device fetch per check, intended for debugging and the
    chaos harness (serve/chaos.py), which forces it ON for every step;
    the production fast path defaults to off and pays nothing."""

    def __init__(self, cfg: ModelConfig, slots: int, max_len: int,
                 page_size: int, *, cache_dtype=jnp.float32,
                 num_pages: int | None = None,
                 kv_quant: str | None = None,
                 debug_invariants: bool = False):
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.page_size = page_size
        self.kv_quant = kv_quant
        self.pages_per_seq = dec.pages_per_seq(max_len, page_size)
        self.num_pages = (slots * self.pages_per_seq
                          if num_pages is None else num_pages)
        self.state = dec.init_paged_cache(cfg, slots, max_len, page_size,
                                          cache_dtype,
                                          num_pages=self.num_pages,
                                          quantize=kv_quant)
        # Each program is a named function, so that its XLA module reads
        # ``jit_<name>`` in a device trace.
        def release_slot(c, s):
            return dec.paged_release_slot(cfg, c, s)

        def adopt_prefix(c, s, ids):
            return dec.paged_adopt_prefix(cfg, c, s, ids)

        def fork_page(c, s, i, src, p):
            return dec.paged_fork_page(cfg, c, s, i, src, pos_to=p)

        def addref(c, ids):
            return dec.paged_addref(cfg, c, ids)

        def deref_pages(c, ids):
            return dec.paged_deref_pages(cfg, c, ids)

        # state donated on every mutation: release/insert return a full
        # new pytree, and the pool is the big buffer — without donation
        # each finish()/admission would pay a pool copy
        self._release = jax.jit(release_slot, donate_argnums=0)
        # one jit entry per PADDED prompt length (a page multiple): the
        # true length rides in as a traced operand, so mixed-length
        # traffic costs at most pages_per_seq distinct traces
        self._insert = {}
        # prefix-sharing entry points (PR 8): page-run adoption, CoW tail
        # fork, and the trie's external refcount pin — all donate the
        # state like release/insert do
        self._adopt = jax.jit(adopt_prefix, donate_argnums=0)
        self._fork = jax.jit(fork_page, donate_argnums=0)
        self._addref = jax.jit(addref, donate_argnums=0)
        self._deref = jax.jit(deref_pages, donate_argnums=0)
        # external refcount provider (set by the scheduler to the prefix
        # trie's page_refs): pages pinned OUTSIDE any slot's table that
        # the conservation audit must account for
        self.external_ref = None
        self.debug_invariants = debug_invariants
        self.invariant_checks = 0

    # -- invariants ---------------------------------------------------------
    def check_invariants(self) -> None:
        """Audit page aliasing / refcount conservation / free-stack
        conservation / pos-vs-table occupancy on the LIVE device state
        (one small fetch — table, free stack, refcounts, positions;
        never the pool).  Raises :class:`InvariantViolation` listing
        every violation found."""
        self.invariant_checks += 1
        ext = self.external_ref() if self.external_ref is not None else None
        bad = dec.paged_invariants(self.cfg, self.state, external_ref=ext)
        if bad:
            raise InvariantViolation(
                "paged pool invariants violated:\n  " + "\n  ".join(bad))

    def _maybe_check(self) -> None:
        if self.debug_invariants:
            self.check_invariants()

    # -- capacity -----------------------------------------------------------
    def pages_needed(self, length: int) -> int:
        return -(-max(length, 1) // self.page_size)

    def free_pages(self) -> int:
        return int(self.state["free_top"])

    # -- accounting ---------------------------------------------------------
    def pages_in_use(self) -> int:
        return self.num_pages - self.free_pages()

    def active_tokens(self) -> int:
        return int(jnp.sum(self.state["pos"]))

    @staticmethod
    def _is_page_leaf(name: str, leaf) -> bool:
        """Pool leaves (rank-5 page pools) and their per-page scale side
        tensors (``scl*``) — everything whose axis 1 is the physical page
        axis and whose bytes scale with pages in use."""
        return hasattr(leaf, "ndim") and (
            leaf.ndim == 5 or name.startswith("scl"))

    def page_bytes(self) -> int:
        """Bytes of ONE page across every attention layer's pool — the
        quantized element type AND the per-page scale side tensor both
        count (dtype-aware: an int8 pool page is ~1/4 of a float32 one
        plus its float32 scale row)."""
        total = 0
        for name, leaf in self.state["blocks"].items():
            if self._is_page_leaf(name, leaf):
                total += (leaf.size // leaf.shape[1]) * leaf.dtype.itemsize
        return total

    def used_cache_bytes(self) -> int:
        """Bytes of cache state actually BACKING live requests: pages in
        use across all layer pools (including the per-page scale side
        tensors of a quantized pool), the page table, and the recurrent
        state — the number that scales with active tokens (the pool
        allocation itself is ``num_pages`` pages; size it to the traffic
        peak)."""
        recurrent = sum(
            pytree_nbytes(leaf)
            for name, leaf in self.state["blocks"].items()
            if not self._is_page_leaf(name, leaf))
        return (self.pages_in_use() * self.page_bytes()
                + self.state["table"].size
                * self.state["table"].dtype.itemsize + recurrent)

    def total_cache_bytes(self) -> int:
        """Full allocation footprint of the cache pytree."""
        return pytree_nbytes(self.state)

    # -- mutation (jit'd, slot-traced: no retrace per slot) -----------------
    def release(self, slot: int) -> None:
        self.state = self._release(self.state, jnp.int32(slot))
        self._maybe_check()

    def insert_prefill(self, slot: int, cache_states, length: int,
                       state_len: int | None = None) -> None:
        """Embed prefill states (computed over ``state_len`` tokens —
        defaults to ``length``) into the slot's pages."""
        state_len = length if state_len is None else state_len
        n_pg = self.pages_needed(state_len)
        if self.free_pages() < n_pg:
            raise RuntimeError(
                f"page pool exhausted: prompt needs {n_pg} pages, "
                f"{self.free_pages()} free")
        fn = self._insert.get(state_len)
        if fn is None:
            fn = self._insert[state_len] = jax.jit(functools.partial(
                dec.paged_insert_prefill, self.cfg, state_len=state_len),
                donate_argnums=0)
        self.state = fn(self.state, jnp.int32(slot), cache_states,
                        jnp.int32(length))
        self._maybe_check()

    # -- prefix sharing (jit'd, fixed-width operands: no retrace) -----------
    def _padded_ids(self, page_ids) -> jax.Array:
        arr = np.full((self.pages_per_seq,), -1, np.int32)
        arr[:len(page_ids)] = np.asarray(page_ids, np.int32)
        return jnp.asarray(arr)

    def adopt_prefix(self, slot: int, page_ids) -> None:
        """Point ``slot``'s table row at a run of SHARED pages (each gets
        +1 refcount) and set its position past them.  The pages are
        read-only to this slot until released — the partial tail, if
        any, must be forked (:meth:`fork_page`) before any write."""
        if len(page_ids) > self.pages_per_seq:
            raise ValueError(f"prefix run of {len(page_ids)} pages "
                             f"exceeds pages_per_seq={self.pages_per_seq}")
        self.state = self._adopt(self.state, jnp.int32(slot),
                                 self._padded_ids(page_ids))
        self._maybe_check()

    def fork_page(self, slot: int, logical_idx: int, src_page: int,
                  pos_to: int) -> None:
        """Copy-on-write fork: pop a fresh page, copy ``src_page``'s
        beats into it across every layer pool, and point ``slot``'s
        ``logical_idx`` table entry at the COPY (position set to
        ``pos_to``).  The shared source is never written in place."""
        if self.free_pages() < 1:
            raise RuntimeError("page pool exhausted: no free page to "
                               "fork the shared tail into")
        self.state = self._fork(self.state, jnp.int32(slot),
                                jnp.int32(logical_idx),
                                jnp.int32(src_page), jnp.int32(pos_to))
        self._maybe_check()

    def addref(self, page_ids) -> None:
        """External +1 pin per page (the trie publishing pages)."""
        for i in range(0, len(page_ids), self.pages_per_seq):
            self.state = self._addref(
                self.state,
                self._padded_ids(page_ids[i:i + self.pages_per_seq]))
        self._maybe_check()

    def deref_pages(self, page_ids) -> None:
        """Drop one reference per page; orphans (refcount hits zero) go
        back on the free stack — the trie-eviction release path."""
        for i in range(0, len(page_ids), self.pages_per_seq):
            self.state = self._deref(
                self.state,
                self._padded_ids(page_ids[i:i + self.pages_per_seq]))
        self._maybe_check()

    def page_refcounts(self) -> np.ndarray:
        """Host copy of the device refcounts (tests / stats)."""
        return np.asarray(self.state["ref"])

    def table_row(self, slot: int) -> np.ndarray:
        """Host copy of one slot's page-table row (publish path)."""
        return np.asarray(self.state["table"][slot])
