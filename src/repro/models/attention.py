"""Attention: GQA/MQA + RoPE + optional qk-norm + sliding window + cross.

Training/prefill use a chunked flash formulation (lax.scan over KV chunks,
lax.map over Q chunks, running log-sum-exp) so the S x S score matrix is
never materialized — required for the 32k prefill cells to fit HBM.
Sliding-window layers iterate only the diagonal band (O(S*W), not O(S^2)).

EARTH integration: the fused KV projection emits the K/V of each head
INTERLEAVED along features ([k0,v0,k1,v1,...]) — one contiguous AoS beat per
token that is written to the interleaved KV cache in a single transaction;
decode splits it with the segment kernel (see kernels/kv_interleaved.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import vx
from repro.models import layers

NEG_INF = -1e30


class AttnParams(NamedTuple):
    wq: jax.Array          # (d, H*D)
    wkv: jax.Array         # (d, K*2D) feature-interleaved [k|v] per head
    wo: jax.Array          # (H*D, d)
    q_norm: jax.Array | None
    k_norm: jax.Array | None


def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   *, qk_norm: bool, dtype) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d_model ** -0.5
    wk = jax.random.normal(kk, (d_model, n_kv, head_dim), dtype) * s
    wv = jax.random.normal(kv, (d_model, n_kv, head_dim), dtype) * s
    # interleave K/V output features -> one coalesced beat per token/head
    wkv = jnp.stack([wk, wv], axis=-1).reshape(d_model, n_kv * 2 * head_dim)
    p = {
        "wq": jax.random.normal(kq, (d_model, n_heads * head_dim), dtype) * s,
        "wkv": wkv,
        "wo": jax.random.normal(ko, (n_heads * head_dim, d_model), dtype)
              * (n_heads * head_dim) ** -0.5,
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), dtype)
        p["k_norm"] = jnp.ones((head_dim,), dtype)
    return p


def qkv_project(params, x: jax.Array, n_heads: int, n_kv: int, head_dim: int,
                positions: jax.Array, rope_theta: float, *,
                policy=None):
    """x: (B, S, d) -> q (B,S,H,D), and the interleaved kv beat (B,S,K,2D).

    The kv beat is cache-layout-ready (AoS); splitting for use in attention
    is a FIELD=2 segment load.
    """
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, n_heads, head_dim)
    kv = (x @ params["wkv"]).reshape(B, S, n_kv, 2 * head_dim)
    k, v = vx.transpose(vx.Segment(n=kv.shape[-1], fields=2), kv,
                        policy=policy)
    if params.get("q_norm") is not None:
        q = layers.head_rms_norm(q, params["q_norm"])
        k = layers.head_rms_norm(k, params["k_norm"])
    q = layers.rope(q, positions, rope_theta)
    k = layers.rope(k, positions, rope_theta)
    kv = vx.transpose(vx.Segment(n=kv.shape[-1], fields=2), [k, v],
                      policy=policy)  # re-pack post-RoPE beat
    return q, k, v, kv


def _flash_body(q, k, v, *, q_pos, kv_pos, causal, window, scale, kv_len):
    """One (Q-chunk x KV-chunk) tile. q: (B,K,G,Qc,D); k,v: (B,Kc,K,D).

    Masking is an ADDITIVE (Qc, Kc) fp32 bias, not a broadcasted where-pred:
    XLA hoists loop-invariant mask tensors out of the chunk scans, and a
    full-rank pred stacked over all (q, kv) tiles is ~25 GiB/device at
    granite train scale (measured); the 2-D bias hoists to ~0.5 MiB/tile
    and fuses into the score add."""
    s = jnp.einsum("bkgqd,bskd->bkgqs", q, k,
                   preferred_element_type=jnp.float32) * scale
    dq = q_pos[:, None]
    dk = kv_pos[None, :]
    mask = dk < kv_len  # padded KV tail is invalid
    if causal:
        mask &= dq >= dk
    if window is not None:
        mask &= (dq - dk) < window
    bias = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)  # (Qc, Kc)
    return s + bias[None, None, None]


def _constrain_bkgsd(t, ctx):
    """Pin the batch dim of a (B, K, G, S, D) tensor to the data axes.

    Without this, XLA's sharding propagation replicates scan-invariant
    captures of the flash backward over the data axis — measured as
    24 GiB/device full-global-batch buffers at granite train scale."""
    if ctx is None or ctx.mesh is None or not ctx.data_axes:
        return t
    from jax.sharding import PartitionSpec as P
    return ctx.constrain(t, P(ctx.data_axes, None, None, None, None))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    q_offset: int = 0, q_chunk: int = 512,
                    kv_chunk: int = 512, ctx=None) -> jax.Array:
    """Chunked flash attention with a memory-safe custom VJP.

    q: (B, Sq, H, D); k, v: (B, Sk, K, D) with H = K*G. Returns (B, Sq, H, D).
    Sliding-window layers only visit the diagonal band of KV chunks in the
    forward. The backward recomputes score tiles (never materializes the
    S x S exp-weights), saving only (q, k, v, out, lse).
    ``ctx`` (ShardCtx) pins batch/head shardings of the big intermediates.
    """
    B, Sq0, H, D = q.shape
    Sk0, K = k.shape[1], k.shape[2]
    q_chunk = min(q_chunk, Sq0)
    kv_chunk = min(kv_chunk, Sk0)
    if ctx is not None and ctx.mesh is not None:
        from jax.sharding import PartitionSpec as P
        ba = ctx.data_axes or None
        q = ctx.constrain(q, P(ba, None, ctx.model_if_divisible(H), None))
        k = ctx.constrain(k, P(ba, None, ctx.model_if_divisible(K), None))
        v = ctx.constrain(v, P(ba, None, ctx.model_if_divisible(K), None))
    # ragged sequences: pad to chunk multiples; padded KV masked by kv_len,
    # padded Q rows sliced off the output (pad/slice autodiff is exact)
    pad_q = (-Sq0) % q_chunk
    pad_k = (-Sk0) % kv_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    out = _flash(q, k, v, causal, window, q_offset, q_chunk, kv_chunk,
                 Sq0, Sk0, ctx)
    return out[:, :Sq0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, window, q_offset, q_chunk, kv_chunk, Sq0, Sk0,
           ctx):
    out, _ = _flash_fwd(q, k, v, causal, window, q_offset, q_chunk,
                        kv_chunk, Sq0, Sk0, ctx)
    return out


def _flash_fwd(q, k, v, causal, window, q_offset, q_chunk, kv_chunk,
               Sq0, Sk0, ctx):
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = D ** -0.5
    qr = q.reshape(B, Sq // q_chunk, q_chunk, K, G, D)

    banded = window is not None and Sk > window + q_chunk
    if banded:
        band = ((window + q_chunk + kv_chunk - 1) // kv_chunk) * kv_chunk
        band = min(band, Sk)

    def do_q_chunk(qi, qc):
        qt = jnp.moveaxis(qc, 1, 3).reshape(B, K, G, q_chunk, D)
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)
        if banded:
            start = jnp.clip(q_offset + qi * q_chunk + q_chunk - band, 0,
                             Sk - band)
            kb = jax.lax.dynamic_slice_in_dim(k, start, band, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, start, band, axis=1)
            kv_pos0 = start
            n_kv_chunks = band // kv_chunk
        else:
            kb, vb, kv_pos0, n_kv_chunks = k, v, 0, Sk // kv_chunk

        def kv_step(carry, si):
            m, l, acc = carry
            ks = jax.lax.dynamic_slice_in_dim(kb, si * kv_chunk, kv_chunk, 1)
            vs = jax.lax.dynamic_slice_in_dim(vb, si * kv_chunk, kv_chunk, 1)
            kv_pos = kv_pos0 + si * kv_chunk + jnp.arange(kv_chunk)
            s = _flash_body(qt, ks, vs, q_pos=q_pos, kv_pos=kv_pos,
                            causal=causal, window=window, scale=scale,
                            kv_len=Sk0)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p, vs,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, K, G, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(n_kv_chunks))
        l_safe = jnp.maximum(l, 1e-30)
        out = acc / l_safe[..., None]
        lse = m + jnp.log(l_safe)                       # (B,K,G,Qc)
        return jnp.moveaxis(out.reshape(B, H, q_chunk, D), 1, 2), lse

    outs, lses = jax.lax.map(lambda args: do_q_chunk(*args),
                             (jnp.arange(Sq // q_chunk),
                              jnp.moveaxis(qr, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, D).astype(q.dtype)
    lse = jnp.moveaxis(lses, 0, 3).reshape(B, K, G, Sq)  # (B,K,G,Sq)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, q_offset, q_chunk, kv_chunk, Sq0, Sk0, ctx,
               res, g):
    """Tile-recomputing backward: dq via q-chunk scan, dk/dv accumulated
    across q chunks. Never materializes more than one score tile."""
    q, k, v, out, lse = res
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = D ** -0.5
    qt = _constrain_bkgsd(
        jnp.moveaxis(q.reshape(B, Sq, K, G, D), 1, 3), ctx)  # (B,K,G,Sq,D)
    gt = _constrain_bkgsd(
        jnp.moveaxis(g.reshape(B, Sq, K, G, D), 1, 3).astype(jnp.float32),
        ctx)
    ot = _constrain_bkgsd(
        jnp.moveaxis(out.reshape(B, Sq, K, G, D), 1, 3).astype(jnp.float32),
        ctx)
    delta = jnp.sum(gt * ot, axis=-1)                        # (B,K,G,Sq)
    n_q = Sq // q_chunk
    n_kv = Sk // kv_chunk

    def q_step(carry, qi):
        dk, dv = carry
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, qi * q_chunk,
                                                    q_chunk, 3)
        q_i = sl(qt).astype(jnp.float32)
        g_i = sl(gt)
        lse_i = jax.lax.dynamic_slice_in_dim(lse, qi * q_chunk, q_chunk, 3)
        delta_i = jax.lax.dynamic_slice_in_dim(delta, qi * q_chunk,
                                               q_chunk, 3)
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(c, si):
            dq_i, dk, dv = c
            ks = jax.lax.dynamic_slice_in_dim(k, si * kv_chunk, kv_chunk,
                                              1).astype(jnp.float32)
            vs = jax.lax.dynamic_slice_in_dim(v, si * kv_chunk, kv_chunk,
                                              1).astype(jnp.float32)
            kv_pos = si * kv_chunk + jnp.arange(kv_chunk)
            s = _flash_body(q_i, ks, vs, q_pos=q_pos, kv_pos=kv_pos,
                            causal=causal, window=window, scale=scale,
                            kv_len=Sk0)
            p = jnp.exp(s - lse_i[..., None])                # (B,K,G,Qc,Kc)
            dv_c = jnp.einsum("bkgqs,bkgqd->bskd", p, g_i)
            dp = jnp.einsum("bkgqd,bskd->bkgqs", g_i, vs)
            ds = p * (dp - delta_i[..., None]) * scale
            dq_i = dq_i + jnp.einsum("bkgqs,bskd->bkgqd", ds, ks)
            dk_c = jnp.einsum("bkgqs,bkgqd->bskd", ds, q_i)
            upd = lambda acc, c_: jax.lax.dynamic_update_slice_in_dim(
                acc, jax.lax.dynamic_slice_in_dim(
                    acc, si * kv_chunk, kv_chunk, 1) + c_,
                si * kv_chunk, 1)
            return (dq_i, upd(dk, dk_c), upd(dv, dv_c)), None

        dq0 = jnp.zeros((B, K, G, q_chunk, D), jnp.float32)
        (dq_i, dk, dv), _ = jax.lax.scan(kv_step, (dq0, dk, dv),
                                         jnp.arange(n_kv))
        return (dk, dv), dq_i

    dk0 = jnp.zeros((B, Sk, K, D), jnp.float32)
    dv0 = jnp.zeros((B, Sk, K, D), jnp.float32)
    if ctx is not None and ctx.mesh is not None and ctx.data_axes:
        from jax.sharding import PartitionSpec as P
        spec = P(ctx.data_axes, None, None, None)
        dk0, dv0 = ctx.constrain(dk0, spec), ctx.constrain(dv0, spec)
    (dk, dv), dq_chunks = jax.lax.scan(q_step, (dk0, dv0), jnp.arange(n_q))
    # dq_chunks: (n_q, B, K, G, Qc, D) -> (B, Sq, H, D)
    dq = jnp.moveaxis(dq_chunks, 0, 3).reshape(B, K, G, Sq, D)
    dq = jnp.moveaxis(dq, 3, 1).reshape(B, Sq, H, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len, *, window: int | None = None) -> jax.Array:
    """Single-token decode. q: (B, H, D); caches: (B, S, K, D).

    Masks positions >= cache_len (and outside the sliding window). This is
    the per-shard body of the sequence-parallel long-context path — callers
    may psum-merge the returned (out, lse) across a mesh axis.
    """
    B, H, D = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    qt = q.reshape(B, K, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qt, k_cache.astype(q.dtype),
                   preferred_element_type=jnp.float32) * D ** -0.5
    pos = jnp.arange(S)
    mask = pos[None, :] < cache_len  # (B?, S) cache_len scalar or (B,1)
    if window is not None:
        mask &= pos[None, :] >= (cache_len - window)
    s = jnp.where(mask[:, None, None, :] if mask.ndim == 2
                  else mask[None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p / jnp.maximum(l, 1e-30),
                     v_cache.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, D).astype(q.dtype)


def chunk_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    q_pos: jax.Array, *,
                    window: int | None = None) -> jax.Array:
    """C-query prefill-chunk attention.  q: (B, C, H, D); caches:
    (B, S, K, D); ``q_pos``: (C,) absolute positions of the queries,
    or (B, C) when each batch row sits at its own offset (the
    speculative K-token verify step; negative entries mark pad queries
    that attend to nothing real — their outputs are garbage and must
    be gated by the caller).

    Query ``i`` attends to cache positions ``j <= q_pos[i]`` (causal
    over the already-written cache, which includes the chunk's own
    beats) and, for sliding-window layers, only within
    ``q_pos[i] - j < window`` — the same attended set
    :func:`decode_attention` masks one query at a time.  Numerics
    mirror decode_attention (fp32 scores, NEG_INF mask, max/exp/sum
    softmax), so a chunked prefill agrees with forced token-by-token
    decode to float tolerance."""
    B, C, H, D = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    qt = q.reshape(B, C, K, G, D)
    s = jnp.einsum("bckgd,bskd->bkgcs", qt, k_cache.astype(q.dtype),
                   preferred_element_type=jnp.float32) * D ** -0.5
    j = jnp.arange(S)
    if q_pos.ndim == 2:                              # (B, C) per-row offsets
        mask = j[None, None, :] <= q_pos[:, :, None]  # (B, C, S)
        if window is not None:
            mask &= (q_pos[:, :, None] - j[None, None, :]) < window
        s = jnp.where(mask[:, None, None], s, NEG_INF)
    else:
        mask = j[None, :] <= q_pos[:, None]          # (C, S)
        if window is not None:
            mask &= (q_pos[:, None] - j[None, :]) < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgcs,bskd->bkgcd", p / jnp.maximum(l, 1e-30),
                     v_cache.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    return jnp.moveaxis(out, 3, 1).reshape(B, C, H, D).astype(q.dtype)


def cross_attention(params, x: jax.Array, enc_k: jax.Array, enc_v: jax.Array,
                    n_heads: int, n_kv: int, head_dim: int,
                    ctx=None) -> jax.Array:
    """Decoder cross-attention over encoder output (whisper). No RoPE."""
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, n_heads, head_dim)
    out = flash_attention(q, enc_k, enc_v, causal=False, window=None,
                          q_chunk=min(512, S),
                          kv_chunk=min(512, enc_k.shape[1]), ctx=ctx)
    return out.reshape(B, S, n_heads * head_dim) @ params["wo"]


def init_cross_attention(key, d_model, n_heads, n_kv, head_dim, dtype) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d_model ** -0.5
    wk = jax.random.normal(kk, (d_model, n_kv, head_dim), dtype) * s
    wv = jax.random.normal(kv, (d_model, n_kv, head_dim), dtype) * s
    return {
        "wq": jax.random.normal(kq, (d_model, n_heads * head_dim), dtype) * s,
        "wkv": jnp.stack([wk, wv], axis=-1).reshape(d_model,
                                                    n_kv * 2 * head_dim),
        "wo": jax.random.normal(ko, (n_heads * head_dim, d_model), dtype)
              * (n_heads * head_dim) ** -0.5,
    }


def encoder_kv(params, enc_out: jax.Array, n_kv: int, head_dim: int,
               *, policy=None):
    """Project encoder output once per decode session (whisper)."""
    B, S, _ = enc_out.shape
    kv = (enc_out @ params["wkv"]).reshape(B, S, n_kv, 2 * head_dim)
    k, v = vx.transpose(vx.Segment(n=kv.shape[-1], fields=2), kv,
                        policy=policy)
    return k, v
