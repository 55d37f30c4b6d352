"""Plain float32 reference of a decoder-only transformer whose every FFN is
a sparse expert layer (Qwen3-MoE), for one device's share of the experts.

The forward pass the benchmark holds the served tokens against, written
from the published architecture and nothing else: it imports no module of
the system under test and reads only the weights that :func:`init` makes
from the run's seed.  Attention, the norms, RoPE, the matrix products and
the float8 control are :mod:`references.dense_decoder`'s, imported: each
layer is the dense reference's layer with a zero FFN, which returns
x + attention(x) exactly, followed by the expert layer below.

The expert layer, per token: a softmax over the router's logits for all
``published.num_experts`` experts, the top ``num_experts_per_tok``,
renormalised over those when ``norm_topk_prob``; then the weighted SwiGLU
outputs (``moe_intermediate_size`` wide) of the chosen experts that this
share holds, the ``num_experts`` from ``first_expert``, computed densely
over the held experts with no capacity and no batching.  Experts held on
other devices add nothing here, as in the program.

Every layer is sparse (``decoder_sparse_step`` 1, ``mlp_only_layers``
empty) and there is no shared expert; other settings are refused.  Each
expert's weights are drawn from a key of its index in the whole model, so
an expert is the same whichever share holds it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from references import dense_decoder as dense

EXPERT_KEYS = ("w_down", "w_gate", "w_up")


def dims(c: dict) -> dict:
    if c["decoder_sparse_step"] != 1 or c["mlp_only_layers"]:
        raise ValueError("the reference has every layer sparse")
    m = dense.dims(c)
    m.update(E=dict(c["published"])["num_experts"], held=c["num_experts"],
             first=c["first_expert"], k=c["num_experts_per_tok"],
             F=c["moe_intermediate_size"],
             norm_topk=bool(c["norm_topk_prob"]))
    return m


def layer_shapes(c: dict) -> dict:
    m = dims(c)
    d, H, K, D, F, n = m["d"], m["H"], m["K"], m["D"], m["F"], m["held"]
    s = {"ln1": (d,), "wq": (d, H * D), "wk": (d, K * D), "wv": (d, K * D),
         "wo": (H * D, d), "ln2": (d,), "router": (d, m["E"]),
         "w_gate": (n, d, F), "w_up": (n, d, F), "w_down": (n, F, d)}
    if m["qk_norm"]:
        s.update(q_norm=(D,), k_norm=(D,))
    return s


def init_layer(c: dict, key, i) -> dict:
    """Layer ``i``'s weights: matrices normal(0, initializer_range), norm
    scales one; expert ``e``'s (of the whole model) from key ``e`` of its
    tensor's key."""
    m = dims(c)
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), i)
    out = {}
    for j, (name, shape) in enumerate(sorted(layer_shapes(c).items())):
        k = jax.random.fold_in(lk, j)
        if len(shape) == 1:
            out[name] = jnp.ones(shape, jnp.float32)
        elif name in EXPERT_KEYS:
            out[name] = m["std"] * jax.vmap(
                lambda e: jax.random.normal(jax.random.fold_in(k, e),
                                            shape[1:], jnp.float32))(
                m["first"] + jnp.arange(m["held"]))
        else:
            out[name] = m["std"] * jax.random.normal(k, shape, jnp.float32)
    return out


def init_outer(c: dict, key) -> dict:
    return dense.init_outer(c, key)


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
    keep = ("moe_intermediate_size", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "first_expert", "decoder_sparse_step")
    return dense.freeze(c) + tuple((k, c[k]) for k in keep) + (
        ("mlp_only_layers", tuple(c["mlp_only_layers"])),
        ("published", (("num_experts", c["published"]["num_experts"]),)))


# -- forward ----------------------------------------------------------------

def _experts(m, quant, x, w):
    """x plus the held experts' weighted SwiGLU outputs."""
    h = dense._rms(x, w["ln2"], m["eps"])
    probs = jax.nn.softmax(dense._mm(h, w["router"], quant), axis=-1)
    topw, topi = jax.lax.top_k(probs, m["k"])                  # (S, k)
    if m["norm_topk"]:
        topw = topw / jnp.sum(topw, -1, keepdims=True)
    held = m["first"] + jnp.arange(m["held"])
    gate = jnp.sum(jnp.where(topi[:, :, None] == held, topw[:, :, None],
                             0.0), axis=1)                      # (S, held)

    def expert(wg, wu, wd):
        a = jax.nn.silu(dense._mm(h, wg, quant)) * dense._mm(h, wu, quant)
        return dense._mm(a, wd, quant)                          # (S, d)
    y = jax.vmap(expert)(w["w_gate"], w["w_up"], w["w_down"])   # (held, S, d)
    return x + jnp.einsum("se,esd->sd", gate, y, precision=dense.HIGHEST)


def _layer(m, quant, x, w):
    d = m["d"]
    zero = {"wg": jnp.zeros((d, 1)), "wu": jnp.zeros((d, 1)),
            "wd": jnp.zeros((1, d))}
    attn = {k: v for k, v in w.items()
            if k not in EXPERT_KEYS and k != "router"}
    x, _ = dense._layer(m, quant, x, dict(attn, **zero))
    return _experts(m, quant, x, w), None


@functools.partial(jax.jit, static_argnums=(0, 4))
def _logits(cj, w, tokens, rows, quant):
    m = dims(dict(cj))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0)
        x, _ = jax.lax.scan(functools.partial(_layer, m, quant), x,
                            w["layers"])
        x = dense._rms(jnp.take(x, rows, axis=0), w["final_norm"], m["eps"])
        head = w["embed"] if m["tied"] else w["lm_head"]
        return dense._mm(x, head.T, quant)


def logits(c: dict, w: dict, tokens, rows, *, quant: str | None = None):
    """Next-token logits (len(rows), vocab) float32 of the sequence
    ``tokens`` at the positions ``rows``, as
    :func:`references.dense_decoder.logits`."""
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown control precision {quant!r}")
    return _logits(freeze(c), w, tokens, rows, quant == "fp8")
