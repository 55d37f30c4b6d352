"""Continuous-batching scheduler over the paged KV runtime.

Split out of the old monolithic ``serve/engine.BatchedServer`` (which
survives there as a thin compat wrapper): this module owns ADMISSION
(free-slot + free-page checks, multi-token prompt prefill through the
existing jit'd prefill), the PER-STEP ACTIVE SET (one jit'd
``paged_decode_step`` over all slots with an ``active`` mask — idle
slots append nothing and advance nothing), SAMPLING (greedy argmax by
default; temperature / top-k with seeded per-slot PRNG keys), and
RECLAMATION (``finish`` releases the slot's pages back to the device
free stack and clears its per-slot state, so a reused slot can never
attend to the previous occupant's cache).

The hardened REQUEST LIFECYCLE (serve/lifecycle.py) layers on top:

  * ``submit`` places typed :class:`~repro.serve.lifecycle.Request`
    objects on a bounded admission queue (backpressure raises
    ``AdmissionError`` with a retry-after hint instead of crashing);
  * ``tick`` pumps the queue, steps the active set, and retires
    finished / expired requests — every admitted request ends in a
    terminal typed state;
  * PREEMPTION-AND-RESTORE: under page pressure a victim slot (lowest
    priority, then most pages held) is released and its request
    requeued carrying the accumulated tokens.  Resume re-runs the
    ORIGINAL prompt through the one jit'd prefill (bit-identical to
    first admission — same ``state_len``, same computation) and then
    REPLAYS the generated tokens through the ordinary jit'd decode step
    (inputs come from the replay cursor, sampled outputs are
    discarded), so post-catch-up decode is BIT-EXACT vs an
    uninterrupted run for every stack — the replay is literally the
    same computation the uninterrupted engine performed (prefill-based
    fast restore would only be allclose: prefill KV != decode KV at the
    ULP level).  Greedy decode preserves determinism across preemption;
    temperature sampling consumes extra PRNG splits during replay.
  * RUNTIME GUARDS (off by default — the steady-state fast path is one
    fused step, zero retraces, zero extra device work): per-slot
    NaN/Inf logit detection that fails ONLY the offending slot (pages
    reclaimed, request -> FAILED; neighbours are bit-unaffected — rows
    of the batched step are independent), a step wall-time watchdog
    reusing ``ft/straggler`` deadline logic, and per-mutation pool
    invariant auditing (``PagedCache.check_invariants``), always-on
    under the chaos harness (serve/chaos.py).

PR 8 — PREFIX SHARING and CHUNKED PREFILL:

  * Prompts now prefill in PAGE-SIZED CHUNKS through ONE fixed-width
    jit (``models/decode.paged_prefill_chunk`` — token width is the
    page size, the true count and slot ride in as traced operands, so
    every chunk of every prompt reuses the same trace and the same
    access plans).  ``tick`` advances each mid-prefill slot by
    ``chunk_pages`` chunks BETWEEN decode steps, so a long prompt no
    longer monopolizes the engine before the first decode token: the
    active set keeps stepping while admission streams pages in.  A
    mid-prefill slot is preemptible (``PREFILLING -> PREEMPTED``) and
    migratable — resume re-runs the chunks, which are bit-identical.
  * With ``prefix_cache=True`` (attention-only stacks) a radix trie
    (serve/prefix_cache.py) maps token prefixes to refcounted page
    runs: admission ADOPTS shared full pages (the slot's table points
    at them — zero new device work), FORKS a copy-on-write private
    tail when the match ends mid-page, and completed prefills PUBLISH
    their prompt pages back to the trie.  Release reclaims only
    orphaned pages; under page pressure the trie evicts LRU unpinned
    leaves before any running slot is preempted.  Decode over adopted
    pages is BIT-EXACT vs a private copy — the gather reads the same
    bits through the same table mechanism.
  * ``AdmissionError.retry_after`` now folds in the pending prefill
    backlog (queued + in-flight chunks, measured in chunk budgets per
    tick) on top of the decode-step EWMA, so backpressure hints stay
    honest when long prompts are queued.

Everything device-side is jit'd ONCE: per-step membership changes ride
in as array operands (token vector, active mask, page table), so steady
state pays zero retraces and zero plan-cache misses
(tests/test_serve.py asserts this).
"""
from __future__ import annotations

import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.ft.straggler import StepWatchdog
from repro.models import decode as dec
from repro.models.transformer import ModelConfig
from repro.serve.lifecycle import (AdmissionError, AdmissionQueue, Request,
                                   RequestState)
from repro.serve.paged_cache import PagedCache
from repro.serve.prefix_cache import PrefixCache

# Host spans of the serve path (``serve.*``).  A TraceAnnotation writes an
# event only while a profiler is running, onto the device trace's clock;
# otherwise it costs about a microsecond and records nothing.
_span = jax.profiler.TraceAnnotation


def sample_tokens(logits: jax.Array, keys, *, temperature: float = 0.0,
                  top_k: int | None = None) -> jax.Array:
    """Per-slot sampling.  logits: (B, V); keys: (B,) PRNG keys.

    ``temperature <= 0`` (the default) is greedy argmax; otherwise
    categorical over ``logits / temperature``, restricted to the top-k
    logits when ``top_k`` is set (``top_k=1`` degenerates to argmax).
    """
    lg = logits.astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)
    lg = lg / temperature
    if top_k is not None and top_k < lg.shape[-1]:
        kth = jnp.sort(lg, axis=-1)[:, -top_k][:, None]
        lg = jnp.where(lg >= kth, lg, -jnp.inf)
    return jax.vmap(jax.random.categorical)(keys, lg).astype(jnp.int32)


class Scheduler:
    """Fixed-slot continuous batching over a shared page pool.

    ``page_size`` / ``num_pages`` size the pool (``num_pages=None`` fully
    provisions ``slots * pages_per_seq``); ``kv_quant`` ("int8" / "fp8")
    selects the QUANTIZED page pool — pages store narrow KV with
    per-page scales, dequant fused into the one page-gather program
    (models/decode.py), ~4x cache memory at bounded logit error;
    ``temperature`` / ``top_k`` /
    ``seed`` configure sampling (greedy by default, deterministic);
    ``prefill_pad`` pads prompts before prefill to bound jit retraces
    (defaults to the page size, so prompt caches always land on whole
    pages — a requirement of the paged insert).

    Lifecycle knobs: ``queue_depth`` bounds the admission queue
    (backpressure beyond it), ``preemption`` lets ``tick`` evict a
    victim under page pressure instead of stalling admission,
    ``guard_nan`` enables the per-slot NaN/Inf logit guard,
    ``watchdog`` (a :class:`~repro.ft.straggler.StepWatchdog`) tracks
    step wall-time deadline breaches, ``debug_invariants`` audits the
    page pool after every mutation, and ``clock`` is the injectable
    time source deadlines are measured against (chaos tests drive a
    fake clock).

    Prefix / prefill knobs (PR 8): ``prefix_cache=True`` enables the
    radix prefix cache (attention-only stacks; silently off elsewhere
    — recurrent state cannot ride in shared pages), ``chunk_pages``
    is the per-tick prefill budget in pages (``tick`` advances each
    mid-prefill slot by that many chunks between decode steps; the
    legacy ``add_request`` still prefills to completion before
    returning, through the same chunk jit).

    Speculative decode knobs (PR 10): ``speculate=K`` with a
    ``draft_cfg`` / ``draft_params`` small model turns decode into a
    K-token verify — the draft proposes K-1 tokens and the target
    checks all K through ONE fused page-gather/verify program per step
    (models/decode.paged_verify_step), with rejected tokens rolled
    back via page table + pos only.  Requires greedy sampling and an
    attention-only draft.  ``submit(..., speculate=k)`` sets a
    per-request width (clamped to the scheduler K; ``speculate=1``
    opts a request out), so speculative and normal slots mix in the
    same verify launch.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int,
                 max_len: int, page_size: int | None = None,
                 num_pages: int | None = None, cache_dtype=jnp.float32,
                 kv_quant: str | None = None,
                 fuse_step: bool = True, temperature: float = 0.0,
                 top_k: int | None = None, seed: int = 0,
                 queue_depth: int | None = None, preemption: bool = True,
                 guard_nan: bool = False,
                 watchdog: StepWatchdog | None = None,
                 debug_invariants: bool = False,
                 prefix_cache: bool = False, chunk_pages: int = 1,
                 speculate: int = 1,
                 draft_cfg: ModelConfig | None = None, draft_params=None,
                 clock: Callable[[], float] = time.monotonic):
        if cfg.encoder is not None:
            raise NotImplementedError("paged serving covers decoder-only "
                                      "models")
        # speculation knobs are validated at construction like sampling:
        # a bad combination must fail loudly here, not at the first
        # verify step deep inside a serving loop
        if speculate < 1:
            raise ValueError(f"speculate must be >= 1, got {speculate}")
        if speculate > 1:
            if draft_cfg is None or draft_params is None:
                raise ValueError("speculate > 1 requires draft_cfg and "
                                 "draft_params (the small draft model)")
            if temperature > 0.0:
                raise ValueError(
                    "speculative decode requires greedy sampling "
                    "(temperature=0): verify accepts a draft iff it equals "
                    "the target argmax — a sampled target has no single "
                    "token to match against")
            if draft_cfg.encoder is not None or \
                    any(k != "attn" for k in draft_cfg.block_pattern):
                raise ValueError(
                    "draft model must be an attention-only decoder: the "
                    "draft cache rolls back rejected tokens via "
                    "paged_truncate (page table + pos only) and recurrent "
                    "draft state cannot be truncated that way")
        # sampling knobs are validated HERE, not inside the jit'd sampler
        # — a bad value must fail loudly at construction, not propagate
        # silently through sample_tokens (top_k <= 0 made the top-k mask
        # drop every logit; negative temperature inverted the
        # distribution)
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0 (0 = greedy), "
                             f"got {temperature}")
        if top_k is not None and top_k <= 0:
            raise ValueError(f"top_k must be a positive int or None, "
                             f"got {top_k}")
        if chunk_pages < 1:
            raise ValueError(f"chunk_pages must be >= 1, got {chunk_pages}")
        from repro import vx
        self.cfg, self.params = cfg, params
        self.slots, self.max_len = slots, max_len
        page_size = min(page_size or 16, max_len)
        self.cache = PagedCache(cfg, slots, max_len, page_size,
                                cache_dtype=cache_dtype,
                                num_pages=num_pages, kv_quant=kv_quant,
                                debug_invariants=debug_invariants)
        self.temperature, self.top_k = float(temperature), top_k
        vx.warm(2 * cfg.hd, strided=False, fields=(2,),
                policy=cfg.vx_policy)
        temperature = self.temperature

        # Each program is a named function, so that its XLA module reads
        # ``jit_<name>`` in a device trace.
        def decode_step(p, c, t, a):
            return dec.paged_decode_step(p, c, t, cfg, None, active=a,
                                         fuse=fuse_step)

        def sample(logits, keys):
            return sample_tokens(logits, keys, temperature=temperature,
                                 top_k=top_k)

        def sample_checked(logits, keys):
            return Scheduler._sample_and_check(
                logits, keys, temperature=temperature, top_k=top_k)

        def split_keys(ks):
            return jnp.swapaxes(jax.vmap(
                lambda k: jax.random.split(k, 2))(ks), 0, 1)

        def prefill_chunk(p, c, t, s, n):
            return dec.paged_prefill_chunk(p, c, t, cfg, None, slot=s,
                                           count=n)

        # cache donated: the pool is the big buffer and the step replaces
        # it wholesale — without donation every append pays a pool copy
        self._step = jax.jit(decode_step, donate_argnums=1)
        self._sample = jax.jit(sample)
        # guard variant: sampling fused with the per-slot finite check so
        # the guard costs one extra reduction, not a second step
        self._sample_guarded = jax.jit(sample_checked)
        self._split_keys = jax.jit(split_keys)
        self._keys = jax.random.split(jax.random.key(seed), slots)
        # chunked prefill: ONE fixed-width jit (token width = page size;
        # slot and true count are traced operands) covers every chunk of
        # every prompt — the same trace and the same vx access plans,
        # so prefill adds nothing to the steady-state plan-cache
        # footprint.  State donated like the decode step.
        self._chunk = jax.jit(prefill_chunk, donate_argnums=1)
        self.chunk_pages = int(chunk_pages)
        self._prefilling: dict[int, int] = {}   # slot -> prefilled tokens
        self.prefill_chunks = 0
        # -- speculative decode (PR 10) --------------------------------------
        # The verify width is STATIC (= ``speculate``): the toks operand is
        # always (slots, K) and per-slot effective widths ride in as the
        # traced ``n_draft`` vector, so mixed speculative/normal slots and
        # replay catch-up all reuse ONE verify trace and ONE set of access
        # plans (tests assert zero PLANS misses across mixed K).  The
        # draft model runs in its OWN page pool (fully provisioned — the
        # draft is small) through the same chunk/step jits as the target.
        self.speculate = int(speculate)
        self.draft_cfg, self.draft_params = draft_cfg, draft_params
        self.draft_cache: PagedCache | None = None
        if self.speculate > 1:
            self.draft_cache = PagedCache(
                draft_cfg, slots, max_len, self.cache.page_size,
                cache_dtype=cache_dtype,
                debug_invariants=debug_invariants)
            vx.warm(2 * draft_cfg.hd, strided=False, fields=(2,),
                    policy=draft_cfg.vx_policy)

            def verify_step(p, c, t, n, a):
                return dec.paged_verify_step(p, c, t, cfg, None, n_draft=n,
                                             active=a, fuse=fuse_step)

            def verify_finite(lg):
                return jnp.all(jnp.isfinite(lg.astype(jnp.float32)),
                               axis=-1)

            def draft_step(p, c, t, a):
                return dec.paged_decode_step(p, c, t, draft_cfg, None,
                                             active=a, fuse=fuse_step)

            def draft_chunk(p, c, t, s, n):
                return dec.paged_prefill_chunk(p, c, t, draft_cfg, None,
                                               slot=s, count=n)

            def draft_truncate(c, np_):
                return dec.paged_truncate(draft_cfg, c, np_)

            self._verify = jax.jit(verify_step, donate_argnums=1)
            self._verify_finite = jax.jit(verify_finite)
            self._dstep = jax.jit(draft_step, donate_argnums=1)
            self._dchunk = jax.jit(draft_chunk, donate_argnums=1)
            self._dtrunc = jax.jit(draft_truncate, donate_argnums=0)
        self._spec_k = [1] * slots   # per-slot verify width (request K)
        self._dpos = [0] * slots     # draft tokens consumed (host mirror)
        self.spec_steps = 0          # verify steps taken
        self.spec_proposed = 0       # draft tokens proposed to verify
        self.spec_accepted = 0       # draft tokens accepted by verify
        # prefix sharing is only sound when every layer's state lives in
        # the page pool: recurrent blocks fold the prefix into per-slot
        # state that pages cannot carry, so the trie is gated to
        # attention-only stacks (windowed included — pages hold full KV)
        self.prefix: PrefixCache | None = None
        if prefix_cache and all(k == "attn" for k in cfg.block_pattern):
            self.prefix = PrefixCache(self.cache.page_size,
                                      self.cache.num_pages)
            self.cache.external_ref = self.prefix.page_refs
        self.active = [False] * slots
        self.tokens: list[list[int]] = [[] for _ in range(slots)]
        self.last_logits = None      # (slots, V) of the latest step
        # -- lifecycle state ------------------------------------------------
        self.clock = clock
        self.preemption = preemption
        self.guard_nan = guard_nan
        self.watchdog = watchdog
        self.queue = AdmissionQueue(
            queue_depth if queue_depth is not None else 4 * slots,
            retry_after_hint=self._retry_after)
        self.requests: dict[int, Request] = {}     # rid -> Request
        self._slot_req: list[Request | None] = [None] * slots
        # replay cursor: index into tokens[s] of the NEXT input token.
        # Normal decode keeps it at len(tokens[s]) - 1; a resumed slot
        # starts behind and catches up one token per step, discarding
        # the (re-)sampled outputs until it does.
        self._fed = [0] * slots
        self._pos = [0] * slots      # host mirror of cache.state["pos"]
        self._taint: np.ndarray | None = None   # chaos NaN-injection hook
        self._newly_terminal: list[Request] = []   # failed outside tick
        self._step_ewma = 0.0
        self.nan_failures = 0
        self.preemptions = 0
        # serve-path counters (stats(), printed by the serve CLI): decode
        # or verify steps taken, and device->host reads made (see _sync)
        self.decode_steps = 0
        self.host_syncs = 0
        self.moe_units: dict[str, np.ndarray] = {}   # folded by stats()
        # per-request latency accounting (host clock, zero device work):
        # TTFT = first decoded token minus submit; inter-token latency is
        # the per-token gap between appends (a K-token speculative commit
        # records gap/K for each — that is exactly the latency win the
        # bench row has to show).  Samples aggregate to p50/p99 in stats().
        self._submit_t: dict[int, float] = {}     # rid -> submit time
        self._last_tok_t: dict[int, float] = {}   # rid -> last append time
        self._ttft: list[float] = []
        self._itl: list[float] = []

    @staticmethod
    def _sample_and_check(logits, keys, *, temperature, top_k):
        lg32 = logits.astype(jnp.float32)
        return (sample_tokens(logits, keys, temperature=temperature,
                              top_k=top_k),
                jnp.all(jnp.isfinite(lg32), axis=-1))

    def _sync(self, read, *args):
        """``read(*args)``, one device->host read of the serve path:
        counted in ``host_syncs`` and spanned as ``serve.host_sync``."""
        with _span("serve.host_sync"):
            self.host_syncs += 1
            return read(*args)

    # -- admission ----------------------------------------------------------
    def free_slot(self) -> int | None:
        for s in range(self.slots):
            if not self.active[s]:
                return s
        return None

    def _reserved_pages(self) -> int:
        """Pages live requests will need for their CURRENT tokens — plus
        the K-token worst case for speculative slots: a verify step may
        append up to ``_spec_k[s]`` tokens before any rollback, so those
        pages must be admissible even if every draft is accepted."""
        return sum(self.cache.pages_needed(len(self.tokens[s])
                                           + self._spec_k[s] - 1)
                   for s in range(self.slots) if self.active[s])

    def _pages_for(self, toks: Sequence[int], k: int = 1) -> int:
        return self.cache.pages_needed(max(len(toks) - 1, 1) + k - 1) + 1

    def _req_k(self, req: Request) -> int:
        """Effective verify width for a request: its own ``speculate``
        clamped into [1, scheduler K]."""
        return max(1, min(int(getattr(req, "speculate", 1)),
                          self.speculate))

    def add_request(self, prompt: int | Sequence[int]) -> int:
        """Admit a request immediately (the legacy surface).  ``prompt``
        is a full token list (or a single int); all but the last token
        are prefilled into the slot's pages through the jit'd prefill,
        and the last token is fed to the next decode step (so
        ``tokens[slot]`` stays prompt + generated).  Raises
        :class:`AdmissionError` (a ``RuntimeError``) with a retry-after
        hint when no slot or not enough free pages — use ``submit`` for
        queued admission with backpressure and preemption."""
        toks = [int(prompt)] if isinstance(prompt, int) else \
            [int(t) for t in prompt]
        if not toks:
            raise ValueError("empty prompt")
        if len(toks) > self.max_len:
            raise ValueError(f"prompt of {len(toks)} tokens exceeds "
                             f"max_len={self.max_len}")
        req = Request(prompt=toks, speculate=self.speculate)
        req.arrival_seq = next(self.queue._seq)
        self.requests[req.rid] = req
        self._submit_t[req.rid] = self.clock()
        try:
            return self._admit_into(req, sync=True)
        except AdmissionError as e:
            if not req.terminal:
                req.to(RequestState.FAILED, error=str(e))
            raise

    def _admit_into(self, req: Request, *, sync: bool = False) -> int:
        """Place a QUEUED request into a free slot and start its
        CHUNKED prefill: the prefix trie serves any shared full pages
        (adopted, +1 refcount each) and a copy-on-write fork of a
        partially-matching tail; the rest streams in page-sized chunks
        — synchronously to completion when ``sync`` (the legacy
        ``add_request`` surface), otherwise one ``chunk_pages`` budget
        per ``tick`` interleaved with decode steps.  Resume after
        preemption re-runs the SAME chunks (one fixed jit — bit-exact
        restart state) and arms the replay cursor over previously
        generated tokens.  Raises AdmissionError when capacity is
        missing; the caller (tick) may preempt and retry."""
        toks = req.tokens
        slot = self.free_slot()
        if slot is None:
            raise AdmissionError("no free slot",
                                 retry_after=self._retry_after())
        # pages are allocated lazily (prefill now, decode appends later):
        # admit against RESERVED pages — what live requests will need for
        # their current tokens plus pages locked in the trie — not just
        # the instantaneous free count.  Trie orphans are evictable, so
        # under pressure LRU leaves are dropped before refusing.
        need = self._pages_for(toks, self._req_k(req))
        avail = self.cache.num_pages - self._reserved_pages()
        if avail < need:
            avail += self._evict_prefix(need - avail)
        if avail < need:
            raise AdmissionError(
                "page pool exhausted; finish a request or grow num_pages",
                retry_after=self._retry_after())
        req.to(RequestState.PREFILLING)
        self.active[slot] = True
        self.tokens[slot] = list(toks)
        self._fed[slot] = 0
        self._pos[slot] = 0
        self._spec_k[slot] = self._req_k(req)
        self._dpos[slot] = 0     # draft catches up lazily via the pump
        self._slot_req[slot] = req
        req.slot = slot
        try:
            self._begin_prefill(slot, req)
            if sync:
                while slot in self._prefilling:
                    if not self._advance_prefill(slot, self.chunk_pages):
                        raise AdmissionError(
                            "page pool exhausted mid-prefill; finish a "
                            "request or grow num_pages",
                            retry_after=self._retry_after())
        except AdmissionError:
            self._release_slot(slot)
            raise
        except Exception as e:       # noqa: BLE001 — typed terminal state
            req.to(RequestState.FAILED, error=f"prefill: {e}")
            self._release_slot(slot)
            raise
        return slot

    # -- chunked prefill ----------------------------------------------------
    def _begin_prefill(self, slot: int, req: Request) -> None:
        """Arm the prefill cursor: serve whatever prefix the trie holds
        (full-page run adopted; partial tail forked CoW when a free
        page exists — otherwise the tail is simply recomputed), then
        leave the remainder to ``_advance_prefill``.  Single-token
        prompts have nothing to prefill and go straight to RUNNING."""
        prompt = req.prompt
        pre = prompt[:-1]
        if not pre:
            self._finish_prefill(slot)
            return
        done = 0
        if self.prefix is not None:
            m = self.prefix.acquire(slot, pre)
            if m.run:
                self.cache.adopt_prefix(slot, list(m.run))
                done = len(m.run) * self.cache.page_size
            if m.fork_src >= 0 and self._sync(self.cache.free_pages) >= 1:
                # fork_page reads the free count once more, then forks
                self._sync(self.cache.fork_page, slot, len(m.run),
                           m.fork_src, done + m.fork_len)
                done += m.fork_len
        self._prefilling[slot] = done
        self._pos[slot] = done
        if done >= len(pre):
            self._finish_prefill(slot)

    def _advance_prefill(self, slot: int, chunks: int) -> bool:
        """Run up to ``chunks`` page-sized prefill chunks for ``slot``
        through the ONE fixed-width chunk jit.  Returns False when the
        pool cannot back the next chunk even after trie eviction — the
        caller preempts the slot (PREFILLING -> PREEMPTED) rather than
        let the device allocator starve the prompt silently."""
        req = self._slot_req[slot]
        pre = req.prompt[:-1]
        ps = self.cache.page_size
        c = self._prefilling[slot]
        free = self.cache.free_pages
        for _ in range(chunks):
            if c >= len(pre):
                break
            with _span("serve.prefill_chunk", slot=slot, rid=req.rid):
                n = min(ps, len(pre) - c)
                newp = self.cache.pages_needed(c + n) - \
                    (0 if c == 0 else -(-c // ps))
                if self._sync(free) < newp:
                    self._evict_prefix(newp - self._sync(free))
                if self._sync(free) < newp:
                    return False
                tok = jnp.asarray(pre[c:c + n] + [0] * (ps - n), jnp.int32)
                self.cache.state = self._chunk(self.params, self.cache.state,
                                               tok, jnp.int32(slot),
                                               jnp.int32(n))
                self.cache._maybe_check()
                c += n
                self._prefilling[slot] = c
                self._pos[slot] = c
                self.prefill_chunks += 1
        if c >= len(pre):
            self._finish_prefill(slot)
        return True

    def _finish_prefill(self, slot: int) -> None:
        """Prefill complete: publish the prompt's full pages to the trie
        (newly inserted ones take the trie's +1 device pin), arm the
        replay cursor, and mark the request RUNNING — the next decode
        step feeds the last prompt token through the ordinary jit."""
        req = self._slot_req[slot]
        self._prefilling.pop(slot, None)
        pre = req.prompt[:-1]
        if self.prefix is not None and pre:
            new = self.prefix.publish(
                slot, pre, self._sync(self.cache.table_row, slot))
            if new:
                self.cache.addref(new)
        self._fed[slot] = len(req.prompt) - 1
        self._pos[slot] = len(req.prompt) - 1
        req.to(RequestState.RUNNING)

    def _evict_prefix(self, n_pages: int) -> int:
        """Drop up to ``n_pages`` LRU unpinned trie leaves and return
        how many pages that freed — the page-pressure valve that runs
        BEFORE any running slot is preempted."""
        if self.prefix is None or n_pages <= 0:
            return 0
        ids = self.prefix.evict(n_pages)
        if ids:
            self.cache.deref_pages(ids)
        return len(ids)

    def _pending_prefill_pages(self) -> int:
        """Prefill chunks still owed: in-flight cursors plus every
        queued prompt — what a newly refused client is waiting behind."""
        ps = self.cache.page_size
        pend = 0
        for s, c in self._prefilling.items():
            req = self._slot_req[s]
            if req is not None:
                pend += -(-max(len(req.prompt) - 1 - c, 0) // ps)
        for r in self.queue._q:
            pend += -(-max(len(r.prompt) - 1, 0) // ps)
        return pend

    def _retry_after(self) -> float:
        """Honest backpressure hint: decode-step EWMA scaled by the
        pending prefill backlog (in per-tick chunk budgets) — a long
        queued prompt delays capacity by its chunk count, not by one
        decode step."""
        ew = self._step_ewma or 0.0
        return ew * (1.0 + self._pending_prefill_pages()
                     / max(self.chunk_pages, 1))

    def submit(self, prompt: Sequence[int], *, max_new_tokens: int | None
               = None, priority: int = 0, deadline: float | None = None,
               ttl: float | None = None,
               speculate: int | None = None) -> Request:
        """Queue a typed request for admission by ``tick``.

        Malformed requests (empty / oversized prompt, non-positive
        ``max_new_tokens``) come back already FAILED — a terminal typed
        state, not an exception, so chaos traffic can always account
        for them.  A full queue raises :class:`AdmissionError`
        (backpressure; pair with
        :func:`repro.serve.lifecycle.retry_with_backoff`)."""
        if ttl is not None:
            deadline = self.clock() + ttl if deadline is None else \
                min(deadline, self.clock() + ttl)
        req = Request(prompt=list(prompt), max_new_tokens=max_new_tokens,
                      priority=priority, deadline=deadline,
                      speculate=self.speculate if speculate is None
                      else int(speculate))
        self.requests[req.rid] = req
        self._submit_t[req.rid] = self.clock()
        if req.speculate < 1:
            req.to(RequestState.FAILED,
                   error=f"speculate must be >= 1, got {req.speculate}")
            return req
        if not req.prompt:
            req.to(RequestState.FAILED, error="empty prompt")
            return req
        if len(req.prompt) > self.max_len:
            req.to(RequestState.FAILED,
                   error=f"prompt of {len(req.prompt)} tokens exceeds "
                         f"max_len={self.max_len}")
            return req
        if max_new_tokens is not None and max_new_tokens <= 0:
            req.to(RequestState.FAILED,
                   error=f"max_new_tokens must be positive, "
                         f"got {max_new_tokens}")
            return req
        try:
            self.queue.push(req)
        except AdmissionError:
            del self.requests[req.rid]       # never admitted: no zombie
            raise
        return req

    # -- preemption ---------------------------------------------------------
    def _victim(self, *, below_priority: int | None = None) -> int | None:
        """Victim slot by policy: lowest priority first, then MOST pages
        held (frees the most), then highest slot id (deterministic)."""
        best = None
        for s in range(self.slots):
            req = self._slot_req[s]
            if not self.active[s] or req is None:
                continue
            if below_priority is not None and \
                    req.priority >= below_priority:
                continue
            key = (-req.priority,
                   self.cache.pages_needed(max(len(self.tokens[s]), 1)),
                   s)
            if best is None or key > best[0]:
                best = (key, s)
        return best[1] if best else None

    def preempt(self, slot: int) -> Request:
        """Evict a running OR mid-prefill slot: release its pages back
        to the free stack (shared prefix pages survive under the trie's
        refcount pin) and requeue its request carrying prompt +
        generated so far.  ``tick`` will resume it (prompt re-prefilled
        bit-exactly through the same chunk jit, generated tokens
        replayed through the ordinary decode step)."""
        req = self._slot_req[slot]
        if req is None or not self.active[slot]:
            raise ValueError(f"slot {slot} is not running a request")
        req.tokens = list(self.tokens[slot])
        req.to(RequestState.PREEMPTED)
        req.slot = None
        self._release_slot(slot)
        self.preemptions += 1
        self.queue.push(req, force=True)
        return req

    def fail_slot(self, slot: int, error: str) -> Request | None:
        """Fail ONLY this slot (NaN guard, chaos slot-death): pages are
        reclaimed, the request goes terminal, neighbours keep stepping
        — the per-slot analogue of the pool's local degradation."""
        req = self._slot_req[slot]
        if req is not None and not req.terminal:
            req.tokens = list(self.tokens[slot])
            req.to(RequestState.FAILED, error=error)
            self._newly_terminal.append(req)
        self._release_slot(slot)
        return req

    def _release_slot(self, slot: int) -> None:
        if self.active[slot]:
            self.cache.release(slot)
            if self.draft_cache is not None:
                self.draft_cache.release(slot)
        if self.prefix is not None:
            self.prefix.release(slot)
        self._prefilling.pop(slot, None)
        self.active[slot] = False
        self.tokens[slot] = []
        self._fed[slot] = 0
        self._pos[slot] = 0
        self._spec_k[slot] = 1
        self._dpos[slot] = 0
        self._slot_req[slot] = None

    # -- decode -------------------------------------------------------------
    def compile_decode(self) -> jax.stages.Compiled:
        """Lower and compile the plain decode step ahead of time, with the
        signature ``step`` gives its jit, so its ``as_text()`` shows which
        kernels that program launches.  The result is for inspection:
        ``step`` dispatches through the jit and does not run it."""
        tok = jnp.zeros((self.slots,), jnp.int32)
        act = jnp.zeros((self.slots,), bool)
        return self._step.lower(self.params, self.cache.state, tok,
                                act).compile()

    def step(self) -> list[int]:
        """Advance every ACTIVE slot; idle slots report -1.

        Slots behind their replay cursor (resumed after preemption) feed
        the next REPLAYED token and discard the sampled output until
        they catch up — same jit'd step, zero retraces.  Mid-prefill
        slots are masked out exactly like idle ones (they occupy a slot
        but decode nothing until their chunks complete).  When any slot
        speculates this step the whole active set goes through the ONE
        fused K-wide verify program instead (``_step_speculative``):
        normal slots ride along at width 1, so mixed speculative/normal
        batches still pay one launch per step."""
        t0 = time.perf_counter()
        decoding = [self.active[s] and s not in self._prefilling
                    for s in range(self.slots)]
        speculative = self.speculate > 1 and any(
            decoding[s] and self._spec_k[s] > 1 for s in range(self.slots))
        self.decode_steps += 1
        with _span("serve.verify" if speculative else "serve.decode"):
            if speculative:
                out = self._step_speculative(decoding)
            else:
                out = self._step_plain(decoding)
            self.cache._maybe_check()
        dt = time.perf_counter() - t0
        self._step_ewma = dt if self._step_ewma == 0.0 else \
            0.8 * self._step_ewma + 0.2 * dt
        if self.watchdog is not None:
            self.watchdog.observe(dt)
        return out

    def _step_plain(self, decoding: list[bool]) -> list[int]:
        """The single-token decode step: the feed built and the step and
        sampling dispatched (``serve.decode.dispatch``), then the sampled
        tokens read back (``serve.decode.readback``), one host sync for
        all slots (two with the NaN guard)."""
        with _span("serve.decode.dispatch"):
            cur = jnp.asarray([self.tokens[s][self._fed[s]]
                               if decoding[s] else 0
                               for s in range(self.slots)], jnp.int32)
            act = jnp.asarray(decoding)
            logits, self.cache.state = self._step(self.params,
                                                  self.cache.state, cur, act)
            if self._taint is not None:      # chaos-only NaN injection hook
                mask = jnp.asarray(self._taint)[:, None]
                logits = jnp.where(mask, jnp.float32(jnp.nan),
                                   logits.astype(jnp.float32)).astype(
                                       logits.dtype)
                self._taint = None
            self.last_logits = logits
            if self.temperature > 0.0:
                self._keys, sub = self._split_keys(self._keys)
            else:
                sub = self._keys
            if self.guard_nan:
                nxt, fin = self._sample_guarded(logits, sub)
            else:
                nxt, fin = self._sample(logits, sub), None
        with _span("serve.decode.readback"):
            nxt = np.asarray(nxt)
            self.host_syncs += 1
            if fin is not None:
                fin = np.asarray(fin)
                self.host_syncs += 1
        out = []
        t_now = self.clock()
        seq_cap = self.cache.pages_per_seq * self.cache.page_size
        for s in range(self.slots):
            t = int(nxt[s])
            if not decoding[s]:
                out.append(-1)
                continue
            if fin is not None and not fin[s]:
                self.nan_failures += 1
                self.fail_slot(s, "non-finite logits")
                out.append(-1)
                continue
            if self._pos[s] < seq_cap:
                self._pos[s] += 1
            if self._fed[s] < len(self.tokens[s]) - 1:
                self._fed[s] += 1      # replay: discard the sample
            else:
                self.tokens[s].append(t)
                self._fed[s] += 1
                self._note_tokens(s, t_now, 1)
            out.append(t)
        return out

    def _step_speculative(self, decoding: list[bool]) -> list[int]:
        """One K-wide verify step over the whole active set.

        Per slot the verify batch is: up to ``_spec_k`` recorded tokens
        when the slot is behind its replay cursor (recorded tokens are
        perfect drafts under greedy decode — replay catches up K tokens
        per launch), otherwise the head token plus ``_spec_k - 1``
        draft-model tokens from :meth:`_draft_pump`.  Commit ``c``
        advances the cursor / appends exactly the tokens the
        non-speculative oracle would produce; rejected pages were
        already rolled back inside the verify jit (page table + pos
        only).  The draft cache is then truncated to the committed
        position the same page-table way."""
        K = self.speculate
        toks = np.zeros((self.slots, K), np.int32)
        nd = np.ones((self.slots,), np.int32)
        recorded = [0] * self.slots
        need = [0] * self.slots
        for s in range(self.slots):
            if not decoding[s]:
                continue
            k = self._spec_k[s]
            req = self._slot_req[s]
            if req is not None and req.max_new_tokens is not None:
                # a commit may append at most the request's remaining
                # budget: K columns past it would overshoot max_new_tokens
                # by up to K-1 tokens vs the non-speculative oracle
                behind = len(self.tokens[s]) - 1 - self._fed[s]
                done = len(self.tokens[s]) - len(req.prompt)
                rem = max(req.max_new_tokens - done, 0)
                k = max(1, min(k, behind + rem))
            avail = len(self.tokens[s]) - self._fed[s]
            r = min(avail, k)
            toks[s, :r] = self.tokens[s][self._fed[s]:self._fed[s] + r]
            recorded[s] = r
            nd[s] = r
            if r == avail and k > r:
                need[s] = k - r          # top up with draft-model tokens
        if any(need):
            with _span("serve.draft"):
                drafts = self._draft_pump(need)
            for s in range(self.slots):
                if need[s]:
                    got = drafts[s]
                    toks[s, recorded[s]:recorded[s] + len(got)] = got
                    nd[s] = recorded[s] + len(got)
        act = jnp.asarray(decoding)
        logits, o, commit, self.cache.state = self._verify(
            self.params, self.cache.state, jnp.asarray(toks),
            jnp.asarray(nd), act)
        if self._taint is not None:      # chaos-only NaN injection hook
            mask = jnp.asarray(self._taint)[:, None, None]
            logits = jnp.where(mask, jnp.float32(jnp.nan),
                               logits.astype(jnp.float32)).astype(
                                   logits.dtype)
            self._taint = None
        self.last_logits = logits[:, 0, :]
        if self.guard_nan:
            fin = self._sync(np.asarray,
                             self._verify_finite(logits))   # (B, K)
        else:
            fin = None
        o_np, cm = self._sync(np.asarray, o), self._sync(np.asarray, commit)
        out = []
        t_now = self.clock()
        seq_cap = self.cache.pages_per_seq * self.cache.page_size
        drafted = False
        for s in range(self.slots):
            if not decoding[s]:
                out.append(-1)
                continue
            c = max(int(cm[s]), 1)
            if fin is not None and not np.all(fin[s, :c]):
                self.nan_failures += 1
                self.fail_slot(s, "non-finite logits")
                out.append(-1)
                continue
            fresh = 0
            for j in range(c):
                if self._fed[s] < len(self.tokens[s]) - 1:
                    self._fed[s] += 1    # replay: record already has it
                else:
                    self.tokens[s].append(int(o_np[s, j]))
                    self._fed[s] += 1
                    fresh += 1
            self._pos[s] = min(self._pos[s] + c, seq_cap)
            if need[s]:
                drafted = True
                self.spec_proposed += need[s]
                self.spec_accepted += max(0, c - recorded[s])
            if fresh:
                self._note_tokens(s, t_now, fresh)
            out.append(int(o_np[s, c - 1]))
        self.spec_steps += 1
        if drafted:
            # rejected draft-cache tail rolls back via page table + pos;
            # a fully-accepted step leaves the draft one token behind,
            # which the next pump's catch-up singles cover
            self.draft_cache.state = self._dtrunc(
                self.draft_cache.state, jnp.asarray(self._pos, jnp.int32))
            self.draft_cache._maybe_check()
            for s in range(self.slots):
                self._dpos[s] = min(self._dpos[s], self._pos[s])
        return out

    def _draft_pump(self, need: list[int]) -> list[list[int]]:
        """Produce ``need[s]`` draft tokens per slot from the draft model.

        First catch the draft cache up to the slot's recorded tokens —
        bulk full pages through the ONE draft chunk jit (a freshly
        admitted or migrated slot replays its whole prompt here), then
        per-token singles — then autoregress the drafts by feeding the
        head token and the draft's own argmaxes.  Singles are batched
        across slots through one draft step jit with an active mask, so
        the steady state (deficit <= 1) costs ``need`` draft launches
        regardless of slot count."""
        dc = self.draft_cache
        ps = dc.page_size
        for s in range(self.slots):
            if need[s] <= 0:
                continue
            target = len(self.tokens[s]) - 1     # tokens before the head
            while self._dpos[s] % ps == 0 and \
                    target - self._dpos[s] >= ps:
                tok = jnp.asarray(
                    self.tokens[s][self._dpos[s]:self._dpos[s] + ps],
                    jnp.int32)
                dc.state = self._dchunk(self.draft_params, dc.state, tok,
                                        jnp.int32(s), jnp.int32(ps))
                self._dpos[s] += ps
        drafts: list[list[int]] = [[] for _ in range(self.slots)]
        pend = {s for s in range(self.slots) if need[s] > 0}
        while pend:
            feed = np.zeros((self.slots,), np.int32)
            act = np.zeros((self.slots,), bool)
            for s in pend:
                i = self._dpos[s]
                feed[s] = self.tokens[s][i] if i < len(self.tokens[s]) \
                    else drafts[s][i - len(self.tokens[s])]
                act[s] = True
            lg, dc.state = self._dstep(self.draft_params, dc.state,
                                       jnp.asarray(feed), jnp.asarray(act))
            nxt = self._sync(np.asarray, jnp.argmax(lg, axis=-1))
            for s in list(pend):
                keep = self._dpos[s] >= len(self.tokens[s]) - 1
                self._dpos[s] += 1
                if keep:
                    drafts[s].append(int(nxt[s]))
                    if len(drafts[s]) >= need[s]:
                        pend.discard(s)
        dc._maybe_check()
        return drafts

    def _note_tokens(self, slot: int, t_now: float, n: int) -> None:
        """Record latency samples for ``n`` tokens appended to ``slot``:
        TTFT on the first decoded token, per-token gaps after (a K-token
        speculative commit records gap/K per token)."""
        req = self._slot_req[slot]
        if req is None:
            return
        rid = req.rid
        last = self._last_tok_t.get(rid)
        if last is None:
            t0 = self._submit_t.get(rid)
            if t0 is not None:
                self._ttft.append(max(t_now - t0, 0.0))
        else:
            self._itl.append(max(t_now - last, 0.0) / n)
        self._last_tok_t[rid] = t_now

    # -- lifecycle pump ------------------------------------------------------
    def tick(self) -> list[Request]:
        """One engine iteration: expire stale queued work, pump
        admission (preempting a lower-priority victim under page
        pressure when ``preemption`` is on), advance each mid-prefill
        slot by ``chunk_pages`` chunks, step the active set, retire
        finished / expired requests.  Returns requests that went
        TERMINAL this tick.

        Spans (``serve.*``, on the device trace's clock while a profiler
        runs): ``serve.tick`` over all of it; ``serve.admit``,
        ``serve.prefill_chunk`` (one per chunk, with ``slot`` and
        ``rid``), ``serve.page_guard``, ``serve.decode`` (or
        ``serve.verify``) and ``serve.retire`` inside it; and
        ``serve.host_sync`` around each device->host read."""
        with _span("serve.tick"):
            with _span("serve.admit"):
                done: list[Request] = list(self.queue.expire(self.clock()))
                self._admit_pump()
            self._prefill_pump()
            if self.preemption and any(self.active):
                with _span("serve.page_guard"):
                    self._page_guard()
            if any(self.active[s] and s not in self._prefilling
                   for s in range(self.slots)):
                self.step()
            with _span("serve.retire"):
                done += self._retire()
            # requests failed mid-step (NaN guard, chaos slot death)
            done.extend(self._newly_terminal)
            self._newly_terminal.clear()
            return done

    def _admit_pump(self) -> None:
        """Admission pump: highest priority first; under pressure, evict
        strictly-lower-priority victims (equal priority never preempts
        equal priority — no livelock)."""
        while True:
            req = self.queue.pop()
            if req is None:
                break
            try:
                self._admit_into(req)
                continue
            except AdmissionError:
                if self.preemption:
                    victim = self._victim(below_priority=req.priority)
                    if victim is not None:
                        self.preempt(victim)
                        try:
                            self._admit_into(req)
                            continue
                        except AdmissionError:
                            pass       # still starved: requeue, stop
                self.queue.push(req, force=True)   # retry next tick
                break

    def _prefill_pump(self) -> None:
        """Chunked-prefill pump: each mid-prefill slot advances by the
        per-tick chunk budget, interleaved with the decode step — a long
        prompt streams in while the active set keeps generating.  A slot
        the pool cannot back even after trie eviction is preempted
        (PREFILLING -> PREEMPTED) and resumes when pages free up, rather
        than silently starving."""
        for s in list(self._prefilling):
            if not self.active[s]:
                continue
            if not self._advance_prefill(s, self.chunk_pages):
                if self.preemption:
                    self.preempt(s)
                else:
                    self.fail_slot(s, "page pool exhausted mid-prefill")

    def _page_guard(self) -> None:
        """In-step page-pressure guard: if this step's page-boundary
        crossers outnumber the free stack, the device allocator would
        degrade locally (starved appends drop).  Evict trie orphans
        first (they free pages without killing work), then preempt
        victims to keep every surviving slot's stream intact."""
        ps = self.cache.page_size
        n_seq = self.cache.pages_per_seq

        def _step_new_pages(s: int) -> int:
            # pages this step may allocate for slot s: a plain slot
            # crosses at most one boundary, a speculative slot may
            # append up to _spec_k tokens before rollback
            p = self._pos[s]
            first = -(-p // ps)
            last = min((p + self._spec_k[s] - 1) // ps, n_seq - 1)
            return max(0, last - first + 1)

        crossers = {s: _step_new_pages(s) for s in range(self.slots)
                    if self.active[s] and s not in self._prefilling
                    and _step_new_pages(s) > 0}
        short = sum(crossers.values()) - self._sync(self.cache.free_pages)
        if short > 0:
            self._evict_prefix(short)
        for _ in range(self.slots):
            live = {s: n for s, n in crossers.items()
                    if self.active[s]}
            if sum(live.values()) <= self._sync(self.cache.free_pages):
                break
            victim = self._victim()
            if victim is None or (victim in live and len(live) == 1):
                break              # nothing to gain: degrade locally
            self.preempt(victim)

    def _retire(self) -> list[Request]:
        """Retire slots whose generation budget is reached, or that run
        past their deadline; returns the requests retired."""
        done = []
        for s in range(self.slots):
            req = self._slot_req[s]
            if req is None or not self.active[s]:
                continue
            caught_up = self._fed[s] >= len(self.tokens[s]) - 1
            if req.max_new_tokens is not None and caught_up and \
                    len(self.tokens[s]) - len(req.prompt) >= \
                    req.max_new_tokens:
                req.tokens = list(self.tokens[s])
                req.to(RequestState.FINISHED)
                self._release_slot(s)
                done.append(req)
            elif req.expired(self.clock()):
                req.tokens = list(self.tokens[s])
                req.to(RequestState.TIMED_OUT,
                       error="deadline expired while running")
                self._release_slot(s)
                done.append(req)
        return done

    def drained(self) -> bool:
        """True when nothing is queued or running."""
        return not any(self.active) and len(self.queue) == 0

    # -- fleet surface (serve/fleet.py) -------------------------------------
    def load(self) -> int:
        """Admission-routing load signal: queued + occupying a slot."""
        return len(self.queue) + sum(self.active)

    def resident_rids(self) -> set[int]:
        """rids RESIDENT on this replica right now: waiting in the
        admission queue or occupying a slot.  The fleet audit asserts
        every live request is resident on EXACTLY one replica."""
        out = {r.rid for r in self.queue._q}
        out |= {r.rid for r in self._slot_req if r is not None}
        return out

    def migrate_queued(self) -> list[Request]:
        """Lift every QUEUED request off this replica (graceful drain of
        a DEGRADED replica: stop admitting, let running finish, move the
        waiting work elsewhere).  Each comes back MIGRATING, carrying
        whatever tokens it had accumulated before a prior preemption."""
        out = self.queue.drain()
        for r in out:
            r.to(RequestState.MIGRATING)
            self.requests.pop(r.rid, None)
        return out

    def adopt(self, req: Request) -> None:
        """Accept a MIGRATING request from another replica: force-queued
        (migration must never be dropped by the admission bound — that
        would turn failover into data loss) and admitted by the next
        tick through the ordinary preemption-resume path."""
        self.requests[req.rid] = req
        self.queue.push(req, force=True)

    def evacuate(self) -> list[Request]:
        """Lift EVERY resident request off this replica — the failover
        path when the replica is declared dead.  Running slots carry
        their accumulated tokens (prompt + generated) into MIGRATING;
        queued work follows.  HOST bookkeeping only: the device pool is
        never touched — a dead replica's pool is discarded wholesale at
        respawn, and resume on the target replica re-prefills the
        original prompt and replays generated tokens through the
        ordinary decode step (the PR 6 replay cursor), so no pool copy
        or KV serialization ever crosses replicas."""
        out: list[Request] = []
        for s in range(self.slots):
            req = self._slot_req[s]
            if req is not None and not req.terminal:
                req.tokens = list(self.tokens[s])
                req.to(RequestState.MIGRATING)   # mid-prefill slots too
                req.slot = None
                out.append(req)
                self.requests.pop(req.rid, None)
            self.active[s] = False
            self.tokens[s] = []
            self._fed[s] = 0
            self._pos[s] = 0
            self._spec_k[s] = 1
            self._dpos[s] = 0
            self._slot_req[s] = None
        self._prefilling.clear()   # cursors die with the replica's pool
        out.extend(self.migrate_queued())
        return out

    def stats(self) -> dict:
        from repro.serve.lifecycle import summarize
        out = summarize(list(self.requests.values()))
        out.update(queue_depth=len(self.queue),
                   queue_rejected=self.queue.rejected,
                   pages_in_use=self.cache.pages_in_use(),
                   free_pages=self.cache.free_pages(),
                   nan_failures=self.nan_failures,
                   invariant_checks=self.cache.invariant_checks,
                   step_ewma_s=self._step_ewma,
                   prefilling=len(self._prefilling),
                   prefill_chunks=self.prefill_chunks,
                   decode_steps=self.decode_steps,
                   host_syncs=self.host_syncs)
        if "moe" in self.cache.state:
            # the expert layers' counters: int32 on the device, added to
            # by every prefill chunk, decode and verify step, read here
            # only, then folded into host integers and zeroed so that a
            # long-lived server's counters do not wrap
            dev = self.cache.state["moe"]
            for name, v in jax.device_get(dev).items():
                self.moe_units[name] = self.moe_units.get(name, 0) + (
                    np.asarray(v, np.int64))
            self.cache.state = dict(self.cache.state,
                                    moe=jax.tree.map(jnp.zeros_like, dev))
            out["moe"] = {k: v.tolist() for k, v in self.moe_units.items()}
        if self.prefix is not None:
            out["prefix"] = self.prefix.stats()
            out["shared_pages"] = int(
                np.sum(self.cache.page_refcounts() > 1))
        if self.watchdog is not None:
            out["watchdog_breaches"] = self.watchdog.breaches
        out["latency"] = self.latency_stats()
        if self.speculate > 1:
            out["speculative"] = {
                "k": self.speculate,
                "verify_steps": self.spec_steps,
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "acceptance": (self.spec_accepted / self.spec_proposed
                               if self.spec_proposed else 0.0),
            }
        return out

    def latency_samples(self) -> dict[str, list[float]]:
        """Raw per-request latency samples (seconds) — the fleet router
        concatenates these across replicas before taking percentiles
        (percentiles of percentiles are not percentiles)."""
        return {"ttft": list(self._ttft), "itl": list(self._itl)}

    def latency_stats(self) -> dict[str, float]:
        """TTFT and inter-token latency p50/p99 over every token this
        scheduler has decoded (seconds, host clock)."""
        out: dict[str, float] = {}
        for name, xs in (("ttft", self._ttft), ("itl", self._itl)):
            if xs:
                out[f"{name}_p50_s"] = float(np.percentile(xs, 50))
                out[f"{name}_p99_s"] = float(np.percentile(xs, 99))
        return out

    # -- reclamation --------------------------------------------------------
    def finish(self, slot: int) -> list[int]:
        """Release the slot: pages back on the free stack, per-slot state
        cleared (position, page-table row, recurrent state, token list).
        Finishing an already-idle slot is explicit: returns ``[]`` —
        never the previous occupant's stale tokens."""
        if not self.active[slot]:
            return []
        toks = self.tokens[slot]
        req = self._slot_req[slot]
        if req is not None and not req.terminal:
            req.tokens = list(toks)
            req.to(RequestState.FINISHED)
        self._release_slot(slot)
        return toks
