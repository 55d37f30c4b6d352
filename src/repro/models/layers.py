"""Shared neural-net layers (pure JAX, no flax): norms, RoPE, embeddings,
MLP/GLU with the EARTH interleaved fused projection option."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import vx


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dtype)


def _pairwise_sum(x: jax.Array) -> jax.Array:
    """Sum over the last axis, keepdims, by folding halves elementwise: the
    order of the float additions is fixed by the program, not by the layout
    XLA picks for ``x``."""
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        s = x[..., :h] + x[..., h:2 * h]
        x = jnp.concatenate([s, x[..., 2 * h:]], -1) if x.shape[-1] % 2 else s
    return x


def head_rms_norm(x: jax.Array, scale: jax.Array,
                  eps: float = 1e-6) -> jax.Array:
    """``rms_norm`` over head_dim (the q/k norm) with a layout-independent
    sum of squares.  A reduction's order follows its operand's layout, and
    that layout depends on the consumer: K feeding a Mosaic interleave keeps
    head_dim on lanes, K fused into XLA's interleave does not.  A fixed
    order makes both lowerings of a prefill write the same bits."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = _pairwise_sum(jnp.square(x)) / x.shape[-1]
    return ((x * jax.lax.rsqrt(var + eps))
            * scale.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., S, H, D), positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                    * (jnp.log(theta) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def embed(tokens: jax.Array, table: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def unembed(x: jax.Array, table: jax.Array) -> jax.Array:
    """Logits = x @ table^T (tied or untied head)."""
    return jnp.einsum("...d,vd->...v", x, table)


# ---------------------------------------------------------------------------
# Feed-forward. The fused GLU path emits gate/up INTERLEAVED along the feature
# dim ([g0,u0,g1,u1,...]) from a single matmul — one contiguous write — and
# de-interleaves with the EARTH segment op (FIELD=2 segment load).
# ---------------------------------------------------------------------------

def glu_ffn(params, x: jax.Array, *, fused: bool = False,
            policy=None) -> jax.Array:
    """SwiGLU. params: {'wi': (d, 2f) or {'wg','wu'}: (d, f), 'wo': (f, d)}."""
    if fused:
        gu = x @ params["wi"]               # (..., 2f) interleaved AoS
        gate, up = vx.transpose(vx.Segment(n=gu.shape[-1], fields=2), gu,
                                policy=policy)
    else:
        gate = x @ params["wg"]
        up = x @ params["wu"]
    return (jax.nn.silu(gate) * up) @ params["wo"]


def mlp_ffn(params, x: jax.Array) -> jax.Array:
    """2-matmul GELU MLP (GPT-BigCode / whisper style)."""
    return jax.nn.gelu(x @ params["wi"], approximate=True) @ params["wo"]


def init_glu(key, d: int, f: int, *, fused: bool, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d ** -0.5
    s_out = f ** -0.5
    if fused:
        wg = jax.random.normal(k1, (d, f), dtype) * s_in
        wu = jax.random.normal(k2, (d, f), dtype) * s_in
        # interleave columns -> [g0,u0,g1,u1,...]
        wi = jnp.stack([wg, wu], axis=-1).reshape(d, 2 * f)
        return {"wi": wi, "wo": jax.random.normal(k3, (f, d), dtype) * s_out}
    return {"wg": jax.random.normal(k1, (d, f), dtype) * s_in,
            "wu": jax.random.normal(k2, (d, f), dtype) * s_in,
            "wo": jax.random.normal(k3, (f, d), dtype) * s_out}


def init_mlp(key, d: int, f: int, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    return {"wi": jax.random.normal(k1, (d, f), dtype) * d ** -0.5,
            "wo": jax.random.normal(k2, (f, d), dtype) * f ** -0.5}
