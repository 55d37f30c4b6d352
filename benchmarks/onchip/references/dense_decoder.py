"""Plain float32 reference of a dense decoder-only transformer.

The forward pass the benchmark holds the served tokens against, written
from the published architecture and nothing else: it imports no module of
the system under test and reads only the weights that :func:`init` makes
from the run's seed.  Every matrix product runs at ``highest`` precision,
so on a TPU it is a float32 product and not a bfloat16 pass.

One layer is computed at a time (``lax.scan`` over the stacked layers), the
whole sequence at once, with an explicit causal mask; the head is applied
only to the rows whose next-token logits are compared.

What the configuration file sets, by its published key:

* ``hidden_size``, ``num_hidden_layers``, ``num_attention_heads``,
  ``num_key_value_heads``, ``head_dim`` (else hidden / heads),
  ``intermediate_size``, ``vocab_size``;
* ``hidden_act``: ``silu``, a gated MLP (gate, up, down), the only kind
  run here;
* ``rms_norm_eps``: the epsilon of every RMS norm but q/k's;
* ``qk_norm``: an RMS norm over head_dim on queries and keys before RoPE
  (Qwen3), epsilon 1e-6;
* ``rope_theta``: rotary embedding on the two halves of head_dim
  (the GPT-NeoX / Hugging Face ``rotate_half`` convention);
* ``tie_word_embeddings``: the head is the embedding table, else
  ``lm_head``.

``quant="fp8"`` is the control: every matrix product's operands are
rounded to float8 e4m3 (scaled per row and per output column) before the
float32 product, the precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def dims(c: dict) -> dict:
    if c["hidden_act"] != "silu":
        raise ValueError(f"no reference for hidden_act {c['hidden_act']!r}")
    d, h = c["hidden_size"], c["num_attention_heads"]
    return dict(
        d=d, L=c["num_hidden_layers"], H=h, K=c["num_key_value_heads"],
        D=c.get("head_dim") or d // h, F=c["intermediate_size"],
        V=c["vocab_size"],
        eps=c["rms_norm_eps"],
        theta=float(c["rope_theta"]), qk_norm=bool(c.get("qk_norm")),
        tied=bool(c["tie_word_embeddings"]),
        std=float(c["initializer_range"]))


def layer_shapes(c: dict) -> dict:
    m = dims(c)
    d, H, K, D, F = m["d"], m["H"], m["K"], m["D"], m["F"]
    s = {"ln1": (d,), "wq": (d, H * D), "wk": (d, K * D), "wv": (d, K * D),
         "wo": (H * D, d), "ln2": (d,), "wg": (d, F), "wu": (d, F),
         "wd": (F, d)}
    if m["qk_norm"]:
        s.update(q_norm=(D,), k_norm=(D,))
    return s


def init_layer(c: dict, key, i) -> dict:
    """Layer ``i``'s weights: matrices normal(0, initializer_range), norm
    scales one.  Each tensor has a key of its own, so a layer is the same
    whether it is made alone or in the stack."""
    m = dims(c)
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    out = {}
    for j, (name, shape) in enumerate(sorted(layer_shapes(c).items())):
        if len(shape) == 1:
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = m["std"] * jax.random.normal(
                jax.random.fold_in(lk, j), shape, jnp.float32)
    return out


def init_outer(c: dict, key) -> dict:
    m = dims(c)
    w = {"embed": m["std"] * jax.random.normal(
             jax.random.fold_in(key, 0), (m["V"], m["d"]), jnp.float32),
         "final_norm": jnp.ones((m["d"],), jnp.float32)}
    if not m["tied"]:
        w["lm_head"] = m["std"] * jax.random.normal(
            jax.random.fold_in(key, 2), (m["V"], m["d"]), jnp.float32)
    return w


@functools.partial(jax.jit, static_argnums=0)
def _init(cj, key):
    c = dict(cj)
    w = init_outer(c, key)
    w["layers"] = jax.lax.map(lambda i: init_layer(c, key, i),
                              jnp.arange(dims(c)["L"]))
    return w


def init(c: dict, key) -> dict:
    """All weights, stacked over layers, float32, in one program."""
    return _init(freeze(c), key)


def freeze(c: dict) -> tuple:
    """A hashable view of the configuration's numbers (a jit static)."""
    keep = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "hidden_act", "rms_norm_eps",
            "rope_theta", "qk_norm", "tie_word_embeddings",
            "initializer_range")
    return tuple((k, c[k]) for k in keep if k in c)


# -- forward ----------------------------------------------------------------

def _q8(x, axis):
    """Round to float8 e4m3 under a max-abs scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, quant):
    """a (..., k) @ b (k, n) in float32 at highest precision; under the
    fp8 control both operands are rounded first."""
    if quant:
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (S, heads, D); position of row t is t."""
    S, _, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m, quant, x, w):
    S = x.shape[0]
    H, K, D = m["H"], m["K"], m["D"]
    h = _rms(x, w["ln1"], m["eps"])
    q = _mm(h, w["wq"], quant).reshape(S, H, D)
    k = _mm(h, w["wk"], quant).reshape(S, K, D)
    v = _mm(h, w["wv"], quant).reshape(S, K, D)
    if m["qk_norm"]:
        q = _rms(q, w["q_norm"], 1e-6)
        k = _rms(k, w["k_norm"], 1e-6)
    q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
    k = jnp.repeat(k, H // K, axis=1)            # head h reads kv h // G
    v = jnp.repeat(v, H // K, axis=1)
    if quant:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * D ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if quant:
        p = _q8(p, -1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(S, H * D), w["wo"], quant)
    h = _rms(x, w["ln2"], m["eps"])
    a = jax.nn.silu(_mm(h, w["wg"], quant)) * _mm(h, w["wu"], quant)
    return x + _mm(a, w["wd"], quant), None


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits(cj, w, tokens, rows, quant):
    m = dims(dict(cj))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0)
        x, _ = jax.lax.scan(functools.partial(_layer, m, quant), x,
                            w["layers"])
        x = _rms(jnp.take(x, rows, axis=0), w["final_norm"], m["eps"])
        head = w["embed"] if m["tied"] else w["lm_head"]
        return _mm(x, head.T, quant)


def logits(c: dict, w: dict, tokens, rows, *, quant: str | None = None):
    """Next-token logits (len(rows), vocab) float32 of the sequence
    ``tokens`` at the positions ``rows``.  Pad ``tokens`` at the end to a
    fixed length to reuse one compiled program: causal attention keeps
    padding out of every earlier row."""
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown control precision {quant!r}")
    return _logits(freeze(c), w, tokens, rows, quant == "fp8")
