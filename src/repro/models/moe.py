"""Mixture-of-Experts with expert parallelism and EARTH-compaction dispatch.

Design (see DESIGN.md §6): activations enter the FFN replicated across the
``model`` mesh axis (standard TP position). Experts are sharded over that
axis, so each device:

  1. routes its data-shard tokens (top-k, renormalized),
  2. selects the (token, slot) units owned by its local experts,
  3. **compacts** their indices to a fixed-capacity buffer — this is the
     EARTH gather network with prefix-sum shift counts (an order-preserving,
     separation-non-increasing mapping; kernels/moe_compact.py),
  4. sorts by local expert and runs grouped GEMMs (lax.ragged_dot),
  5. scatter-adds weighted results and psums over the model axis.

The only collective is the same (T, d) all-reduce a dense TP FFN needs —
no all-to-all, no (T, E, C) one-hot dispatch tensor (the "crossbar" EARTH
removes). Token drop only on per-device capacity overflow (slack-bounded).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import vx


class MoESpec(NamedTuple):
    n_experts: int
    top_k: int
    d_ff: int
    capacity_slack: float = 2.0
    aux_coef: float = 0.01
    dispatch: str = "earth"   # "earth" (shift network) | "sort" (argsort)


def init_moe(key, d_model: int, spec: MoESpec, dtype) -> dict:
    kr, kg, ku, ko = jax.random.split(key, 4)
    E, f = spec.n_experts, spec.d_ff
    s = d_model ** -0.5
    return {
        "router": jax.random.normal(kr, (d_model, E), jnp.float32) * s,
        "wg": jax.random.normal(kg, (E, d_model, f), dtype) * s,
        "wu": jax.random.normal(ku, (E, d_model, f), dtype) * s,
        "wo": jax.random.normal(ko, (E, f, d_model), dtype) * f ** -0.5,
    }


def _capacity(T: int, k: int, n_shards: int, slack: float) -> int:
    cap = int(math.ceil(T * k / n_shards * slack))
    cap = min(max(cap, 8), T * k)
    return ((cap + 7) // 8) * 8 if cap % 8 else cap


def _compact_ids(mine: jax.Array, cap: int, dispatch: str) -> tuple[jax.Array, jax.Array]:
    """Pack indices of set bits of ``mine`` (n,) to the front; take cap."""
    n = mine.shape[0]
    if dispatch == "earth":
        # runtime-count member of the plan bank (core/accessfuse.py):
        # take-masks derived once from the prefix-sum counts, ids pay one
        # shift+select per layer, no conflict reductions
        packed = vx.compact(vx.Compact(n=n, cap=cap), mine)
    else:  # argsort baseline (the XLA-native path)
        order = jnp.argsort(~mine, stable=True)
        packed = order[:cap].astype(jnp.int32)
    total = jnp.sum(mine.astype(jnp.int32))
    pv = jnp.arange(packed.shape[0], dtype=jnp.int32) < total
    return packed, pv


def moe_ffn_local(router, wg, wu, wo, x, spec: MoESpec, *,
                  model_axis: str | None, data_axes: tuple,
                  n_shards: int) -> tuple[jax.Array, jax.Array]:
    """Per-device MoE body. x: (T, d). Returns (y (T, d), aux loss scalar)."""
    T, d = x.shape
    E, k = spec.n_experts, spec.top_k
    e_loc = E // n_shards
    my = jax.lax.axis_index(model_axis) if model_axis else 0

    logits = (x @ router.astype(x.dtype)).astype(jnp.float32)     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)                          # (T, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

    # ---- aux (load-balance + z) losses, identical across model shards ----
    dispatch_frac = jnp.zeros((E,), jnp.float32).at[topi.reshape(-1)].add(
        1.0 / (T * k))
    aux = E * jnp.sum(dispatch_frac * jnp.mean(probs, axis=0))
    zloss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = spec.aux_coef * (aux + 1e-3 * zloss)
    if data_axes:
        aux = jax.lax.pmean(aux, data_axes)

    # ---- unit selection & EARTH compaction ----
    expert = topi.reshape(-1).astype(jnp.int32)                   # (T*k,)
    weight = topw.reshape(-1)
    mine = (expert >= my * e_loc) & (expert < (my + 1) * e_loc)
    cap = _capacity(T, k, n_shards, spec.capacity_slack)
    packed, pv = _compact_ids(mine, cap, spec.dispatch)           # (cap,)

    tok = packed // k
    xe = jnp.take(x, tok, axis=0) * pv[:, None].astype(x.dtype)   # (cap, d)
    le = jnp.take(expert, packed) - my * e_loc
    le = jnp.where(pv, le, e_loc)                                 # sentinel
    order = jnp.argsort(le, stable=True)
    xs = jnp.take(xe, order, axis=0)
    gs = jnp.bincount(jnp.take(le, order), length=e_loc + 1)[:e_loc]
    gs = gs.astype(jnp.int32)

    # grouped GEMMs accumulate fp32 on the MXU but emit x.dtype — fp32
    # (cap, d_ff) activations otherwise dominate peak memory
    gate = jax.lax.ragged_dot(xs, wg, gs, preferred_element_type=x.dtype)
    up = jax.lax.ragged_dot(xs, wu, gs, preferred_element_type=x.dtype)
    ye = jax.lax.ragged_dot(jax.nn.silu(gate) * up, wo, gs,
                            preferred_element_type=x.dtype)       # (cap, d)

    # ---- unsort + weighted combine (reduction done by the caller) ----
    w_packed = jnp.take(weight, packed) * pv.astype(weight.dtype)
    w_sorted = jnp.take(w_packed, order)
    # accumulate in x.dtype (bf16): each token receives <= top_k terms, and
    # fp32 (T, d) accumulators dominate peak memory at Jamba scale
    contrib = (ye.astype(jnp.float32)
               * w_sorted[:, None]).astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[jnp.take(tok, order)].add(contrib)
    return y, aux


def moe_layer(params, x: jax.Array, spec: MoESpec, ctx) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d). ctx: dist.sharding.ShardCtx or None (single device).

    The model-axis reduction of partial expert outputs uses psum_scatter
    over the sequence dim when divisible (the reduce-scatter half of the
    Megatron-SP pattern) — half the wire bytes and a seq-sharded result,
    matching the inter-block activation sharding."""
    B, S, d = x.shape
    m_ax = ctx.model_axis if ctx else None
    m_sz = ctx.model_size if ctx else 1
    seq_scatter = (m_ax is not None and S % m_sz == 0 and S >= m_sz)

    def body(router, wg, wu, wo, xl):
        Tl = xl.shape[0] * xl.shape[1]
        y, aux = moe_ffn_local(
            router, wg, wu, wo, xl.reshape(Tl, d), spec,
            model_axis=m_ax,
            data_axes=ctx.data_axes if ctx else (),
            n_shards=m_sz)
        y = y.reshape(xl.shape)
        if m_ax is not None:
            if seq_scatter:
                y = jax.lax.psum_scatter(y, m_ax, scatter_dimension=1,
                                         tiled=True)
            else:
                y = jax.lax.psum(y, m_ax)
        return y, aux

    if ctx is None or ctx.mesh is None:
        return body(params["router"], params["wg"], params["wu"],
                    params["wo"], x)

    from jax.sharding import PartitionSpec as P

    ba = ctx.data_axes if ctx.data_axes else None
    bspec = P(ba, None, None)
    ospec = P(ba, ctx.model_axis if seq_scatter else None, None)
    sm = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(), P(ctx.model_axis), P(ctx.model_axis),
                  P(ctx.model_axis), bspec),
        out_specs=(ospec, P()),
        check_vma=False)
    return sm(params["router"], params["wg"], params["wu"], params["wo"], x)
