"""Hands a sparse-expert decoder configuration (Qwen3-MoE) to the system
under test.

``model_config`` builds the program's ``ModelConfig``, with an expert
layer told which experts it holds (``num_experts`` of them from
``first_expert``) and a router over the published count; ``program_params``
makes the weights on the device, in one jitted program from the run's
seed, in the program's layout and in the type it serves them in (the
router stays float32, as the program keeps it).  The values are the
reference's (:func:`references.moe_decoder.init_layer`), laid out as the
program reads them: K and V columns interleaved per head, each held
expert's gate, up and down matrices stacked over the held range.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from references import moe_decoder as ref


def model_config(c: dict):
    from repro.models.moe import MoESpec
    from repro.models.transformer import ModelConfig
    m, p = ref.dims(c), c["program"]
    if not m["norm_topk"]:
        raise ValueError("the program renormalises the top-k weights")
    return ModelConfig(
        name=c["name"], d_model=m["d"], n_layers=m["L"], n_heads=m["H"],
        n_kv_heads=m["K"], head_dim=m["D"], d_ff=m["F"], vocab=m["V"],
        block_pattern=("attn",), moe_pattern=(True,), mlp="swiglu",
        moe=MoESpec(n_experts=m["E"], top_k=m["k"], d_ff=m["F"],
                    held=m["held"], first_expert=m["first"]),
        qk_norm=m["qk_norm"], rope_theta=m["theta"], norm_eps=m["eps"],
        tie_embeddings=m["tied"], param_dtype=p["param_dtype"],
        compute_dtype=p["compute_dtype"])


def _program_layer(c: dict, w: dict) -> dict:
    m, p = ref.dims(c), c["program"]
    d, K, D = m["d"], m["K"], m["D"]
    dt = jnp.dtype(p["param_dtype"])
    wkv = jnp.stack([w["wk"].reshape(d, K, D), w["wv"].reshape(d, K, D)],
                    -1).reshape(d, K * 2 * D)
    attn = {"wq": w["wq"], "wkv": wkv, "wo": w["wo"]}
    if m["qk_norm"]:
        attn.update(q_norm=w["q_norm"], k_norm=w["k_norm"])
    out = jax.tree.map(lambda a: a.astype(dt), {
        "ln1": w["ln1"], "attn": attn, "ln2": w["ln2"],
        "moe": {"wg": w["w_gate"], "wu": w["w_up"], "wo": w["w_down"]}})
    out["moe"]["router"] = w["router"]
    return out


@functools.partial(jax.jit, static_argnums=0)
def _params(cj, key):
    c = dict(cj)
    c["program"] = dict(c["program"])
    dt = jnp.dtype(c["program"]["param_dtype"])
    params = {k: v.astype(dt) for k, v in ref.init_outer(c, key).items()}
    params["blocks"] = {"pos0": jax.lax.map(
        lambda i: _program_layer(c, ref.init_layer(c, key, i)),
        jnp.arange(ref.dims(c)["L"]))}
    return params


def _hashable(c: dict) -> tuple:
    """The configuration's numbers and program group, as a jit static."""
    return ref.freeze(c) + (("program", tuple(sorted(c["program"].items()))),)


def program_params(c: dict, key) -> dict:
    """The program's parameter pytree, checked against the structure the
    program's own initialiser would give."""
    from repro.models.transformer import init_params
    params = _params(_hashable(c), key)
    want = jax.eval_shape(lambda k: init_params(model_config(c), k),
                          jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError(f"weights for {c['name']} do not match the "
                         f"program's parameter layout")
    return params
