"""Interleaved (AoS) KV-cache ops — EARTH segment access applied to serving.

Layout: cache[..., t, 2*d] holds [k0, v0, k1, v1, ...] per token — K and V
of a token are ONE contiguous beat, so a decode-step append is a single
coalesced write (the paper's one-transaction-per-segment), and attention-time
splitting is a FIELD=2 segment load.  All routing goes through the
declarative vx API: a ``Segment(fields=2)`` spec, a policy (the model's
``cfg.vx_policy``) picking the lowering — under ``pallas`` the split runs
the segment kernel's transpose route (both K and V from one pass through
the transpose unit) and the pack the FUSED segment kernel (one
compiled-permutation pass, core/shiftplan.py), never two sequential gather
networks.
"""
from __future__ import annotations

import jax

from repro import vx


def _spec(n: int) -> vx.Segment:
    return vx.Segment(n=n, fields=2)


def interleave_kv(k: jax.Array, v: jax.Array, *, policy=None) -> jax.Array:
    """(..., d) x2 -> (..., 2d) AoS beat."""
    return vx.transpose(_spec(2 * k.shape[-1]), [k, v], policy=policy)


def split_kv(kv: jax.Array, *, policy=None) -> tuple[jax.Array, jax.Array]:
    """(..., 2d) -> (k, v)."""
    k, v = vx.transpose(_spec(kv.shape[-1]), kv, policy=policy)
    return k, v


def split_kv_step(kvs: list[jax.Array], *, policy=None, shard=None
                  ) -> list[tuple[jax.Array, jax.Array]]:
    """Whole-step KV split: EVERY layer's (…, 2d) cache in one fused
    FIELD=2 segment load — one kernel launch and one mask upload per decode
    step instead of one per layer (core/accessfuse.py groups same-shape
    caches; mixed window sizes form one group per shape).

    ``shard`` (a ``vx.Shard`` on the cache's sequence axis) lowers the
    merged split shard-locally under ``shard_map`` — the seq-parallel
    long-context cache transposes in place, never gathered or sliced
    globally (the sharding-aware lowering).

    The launch runs under the name scope ``kv_split``, which names its
    HLO instruction, and so its ``XLA Ops`` event in a device trace,
    ``kv_split.<n>`` (the segment kernel also serves FIELD=2 loads that
    are not KV, such as the SwiGLU gate/up split, so the name comes from
    here and not from the kernel)."""
    from repro.core import accessfuse
    with jax.named_scope("kv_split"):
        return accessfuse.fuse_split_kv(kvs, policy=vx.resolve(policy),
                                        shard=shard)


def gather_paged_kv(pools: list[jax.Array], table: jax.Array,
                    page_size: int, *, policy=None, shard=None,
                    fused: bool = True, scales=None) -> list[jax.Array]:
    """Whole-step paged KV read: every layer's page pool gathered through
    ONE shared page table.

    ``pools``: same-shape ``(NS, P, page_size, K, 2d)`` pool leaves (all
    layers append in lockstep, so one ``(B, pages)`` table serves them
    all).  ``fused=True`` stacks the pools and runs ONE page-granular
    gather program (``vx.gather_many`` + ``vx.program.fuse``); the
    heterogeneous per-request lengths live in the runtime table, so the
    compiled program is keyed only by the page geometry and is reused
    across every request and step.  ``shard`` (a ``vx.Shard`` on the pool
    page axis, ``axis=-4``) gathers shard-locally from the owned page
    block — the sharded pool is never sliced globally.

    Returns the gathered interleaved ``(NS, B, pages*page_size, K, 2d)``
    sequences, one per pool; split K/V with :func:`split_kv_step` (still
    one fused FIELD=2 launch for the whole step).

    QUANTIZED pools (int8/fp8) pass their per-page ``(NS, P, K)`` scale
    tensors as ``scales=`` (one per pool, stacked like the pools) — the
    dequant rides the same single gather program and the returned
    sequences are float.
    """
    spec = vx.Paged(page_size=page_size, pages=table.shape[-1], trail=2)
    if fused:
        return vx.gather_many(spec, pools, table=table, scales=scales,
                              policy=policy, shard=shard)
    if scales is None:
        scales = [None] * len(pools)
    return [vx.gather(spec, p, table=table, scales=s, policy=policy,
                      shard=shard)
            for p, s in zip(pools, scales)]


def append_paged_token(pool: jax.Array, k: jax.Array, v: jax.Array,
                       table: jax.Array, pos, *, policy=None, scales=None):
    """Write one token's interleaved KV beat through the page table.

    pool: (..., P, page_size, H, 2d); k, v: (B, H, d); pos: (B,) int32
    per-slot positions (rows with ``pos < 0`` or an unallocated page are
    dropped — an idle serving slot appends nothing).  One page-routed
    scatter per layer, same coalescing as :func:`append_token`.

    A QUANTIZED pool passes its per-page scales and gets back
    ``(pool, scales)`` — the beat quantizes on write, the page scale
    widens monotonically (vx/lower.py).
    """
    beat = interleave_kv(k, v, policy=policy)             # (B, H, 2d)
    spec = vx.Paged(page_size=pool.shape[-3], pages=table.shape[-1],
                    trail=2)
    return vx.scatter(spec, pool, beat, table=table, pos=pos,
                      scales=scales, policy=policy)


def append_token(cache: jax.Array, k: jax.Array, v: jax.Array, pos,
                 *, policy=None) -> jax.Array:
    """Write one token's interleaved KV beat at position ``pos``.

    cache: (B, S, H, 2d); k, v: (B, H, d); pos: scalar int (same for batch).
    One dynamic_update_slice per layer instead of two (K and V) — the
    coalescing win, measured in benchmarks/bench_segment.py.
    """
    beat = interleave_kv(k, v, policy=policy)             # (B, H, 2d)
    beat = beat[:, None]                                  # (B, 1, H, 2d)
    return jax.lax.dynamic_update_slice_in_dim(cache, beat.astype(cache.dtype),
                                               pos, axis=1)
