"""Flash attention (chunked, custom VJP) vs naive reference — outputs AND
gradients, across causal/window/GQA/ragged variants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers
from repro.models.attention import decode_attention, flash_attention


def naive_attention(q, k, v, *, causal, window, q_offset=0):
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    qt = q.reshape(B, Sq, K, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qt, k).astype(jnp.float32) * D**-0.5
    qpos = (q_offset + jnp.arange(Sq))[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, D).astype(q.dtype)


CASES = [
    # (Sq, Sk, H, K, D, causal, window, q_chunk, kv_chunk)
    (64, 64, 4, 2, 16, True, None, 16, 16),
    (64, 64, 4, 1, 16, True, None, 32, 16),     # MQA
    (64, 64, 4, 4, 16, False, None, 16, 32),    # bidirectional (encoder)
    (128, 128, 2, 2, 8, True, 32, 32, 16),      # sliding window (banded)
    (48, 48, 4, 2, 16, True, None, 16, 16),     # ragged-ish
    (50, 70, 4, 2, 16, False, None, 16, 16),    # ragged + cross shapes
]


@pytest.mark.parametrize("case", CASES)
def test_flash_matches_naive_fwd_and_grad(case):
    Sq, Sk, H, K, D, causal, window, qc, kc = case
    keys = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(keys[0], (2, Sq, H, D))
    k = jax.random.normal(keys[1], (2, Sk, K, D))
    v = jax.random.normal(keys[2], (2, Sk, K, D))

    def f_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, window=window,
                            q_chunk=qc, kv_chunk=kc)
        return jnp.sum(jnp.sin(o))

    def f_naive(q, k, v):
        o = naive_attention(q, k, v, causal=causal, window=window)
        return jnp.sum(jnp.sin(o))

    o1 = flash_attention(q, k, v, causal=causal, window=window,
                         q_chunk=qc, kv_chunk=kc)
    o2 = naive_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-4)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} mismatch: {case}")


def test_flash_under_remat_and_jit():
    q = jax.random.normal(jax.random.key(1), (2, 64, 4, 16))
    k = jax.random.normal(jax.random.key(2), (2, 64, 2, 16))
    v = jax.random.normal(jax.random.key(3), (2, 64, 2, 16))

    @jax.jit
    def f(q, k, v):
        g = jax.checkpoint(lambda *a: jnp.sum(
            flash_attention(*a, causal=True, q_chunk=16, kv_chunk=16) ** 2))
        return jax.grad(g, argnums=0)(q, k, v)

    out = f(q, k, v)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_decode_matches_flash_last_row():
    """decode_attention(q_t, cache) == flash row for the last position."""
    B, S, H, K, D = 2, 32, 4, 2, 16
    keys = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(keys[0], (B, S, H, D))
    k = jax.random.normal(keys[1], (B, S, K, D))
    v = jax.random.normal(keys[2], (B, S, K, D))
    full = flash_attention(q, k, v, causal=True, q_chunk=8, kv_chunk=8)
    dec = decode_attention(q[:, -1], k, v, jnp.asarray(S))
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, -1]),
                               rtol=2e-4, atol=2e-4)


def test_decode_window_masking():
    B, S, H, K, D = 1, 16, 2, 2, 8
    keys = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(keys[0], (B, H, D))
    k = jax.random.normal(keys[1], (B, S, K, D))
    v = jax.random.normal(keys[2], (B, S, K, D))
    # window=4 at cache_len=10 must equal full attention over keys 6..9
    dec_w = decode_attention(q, k, v, jnp.asarray(10), window=4)
    dec_f = decode_attention(q, k[:, 6:10], v[:, 6:10], jnp.asarray(4))
    np.testing.assert_allclose(np.asarray(dec_w), np.asarray(dec_f),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("d", [128, 96, 7])
def test_head_rms_norm_matches_rms_norm(d):
    """The fixed-order q/k norm computes the same norm as ``rms_norm``
    (to float32 rounding), for head widths that are and are not powers of
    two."""
    x = jax.random.normal(jax.random.key(d), (3, 5, 4, d), jnp.float32)
    scale = 1.0 + jax.random.normal(jax.random.key(1), (d,), jnp.float32)
    np.testing.assert_allclose(layers.head_rms_norm(x, scale),
                               layers.rms_norm(x, scale), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(layers._pairwise_sum(x)[..., 0],
                               jnp.sum(x, axis=-1), rtol=1e-5, atol=1e-5)
