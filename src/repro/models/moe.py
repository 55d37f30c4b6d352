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

HELD RANGE: a layer holds the weights of a contiguous range of experts
(``MoESpec.first_expert``, ``MoESpec.held``) while its router spans all
``n_experts`` — one device's share of an expert-parallel deployment.  The
mesh path is the same range cut once more: model-axis device ``i`` holds
the ``i``-th part of it.  The layer computes only its held experts'
weighted outputs; what the other experts add lies on other devices.  The
serve path (``models/decode._moe_ffn``) runs the layer on one device,
where the capacity holds every unit (dropless by construction), and
routes masked tokens (idle slots, pad tokens) nowhere.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import vx


class MoESpec(NamedTuple):
    n_experts: int            # experts the router spans
    top_k: int
    d_ff: int
    capacity_slack: float = 2.0
    aux_coef: float = 0.01
    dispatch: str = "earth"   # "earth" (shift network) | "sort" (argsort)
    held: int = 0             # experts whose weights the layer holds (0: all)
    first_expert: int = 0     # first expert of the held range

    @property
    def n_held(self) -> int:
        return self.held or self.n_experts


def init_moe(key, d_model: int, spec: MoESpec, dtype) -> dict:
    """Router over all ``n_experts``; expert weights of the held range."""
    if not 0 <= spec.first_expert <= spec.n_experts - spec.n_held:
        raise ValueError(f"held experts {spec.first_expert}.."
                         f"{spec.first_expert + spec.n_held - 1} lie outside "
                         f"the router's {spec.n_experts}")
    kr, kg, ku, ko = jax.random.split(key, 4)
    E, f = spec.n_held, spec.d_ff
    s = d_model ** -0.5
    return {
        "router": jax.random.normal(kr, (d_model, spec.n_experts),
                                    jnp.float32) * s,
        "wg": jax.random.normal(kg, (E, d_model, f), dtype) * s,
        "wu": jax.random.normal(ku, (E, d_model, f), dtype) * s,
        "wo": jax.random.normal(ko, (E, f, d_model), dtype) * f ** -0.5,
    }


def _capacity(T: int, k: int, n_shards: int, slack: float) -> int:
    cap = int(math.ceil(T * k / n_shards * slack))
    cap = min(max(cap, 8), T * k)
    return ((cap + 7) // 8) * 8 if cap % 8 else cap


def _compact_ids(mine: jax.Array, cap: int, dispatch: str) -> jax.Array:
    """Pack indices of set bits of ``mine`` (n,) to the front; take cap."""
    n = mine.shape[0]
    if dispatch == "earth":
        # runtime-count member of the plan bank (core/accessfuse.py):
        # take-masks derived once from the prefix-sum counts, ids pay one
        # shift+select per layer, no conflict reductions
        return vx.compact(vx.Compact(n=n, cap=cap), mine)
    # argsort baseline (the XLA-native path)
    return jnp.argsort(~mine, stable=True)[:cap].astype(jnp.int32)


def moe_ffn_local(router, wg, wu, wo, x, spec: MoESpec, *,
                  model_axis: str | None, data_axes: tuple,
                  n_shards: int, valid: jax.Array | None = None
                  ) -> tuple[jax.Array, jax.Array, dict]:
    """Per-device MoE body over the ``wg.shape[0]`` experts this device
    holds, from ``spec.first_expert`` (plus this device's offset on the
    model axis).  x: (T, d).

    ``valid`` (T,) keeps masked tokens out of routing: they take no
    capacity and no expert rows.  The buffer of routed units is the
    mesh-derived capacity with slack, over which units drop; at
    ``n_shards`` 1 it holds all ``T * top_k`` units, as many as can reach
    the held experts, so nothing drops.  Returns (y (T, d), aux loss
    scalar, units): ``units`` holds int32 counts of the units ``routed``
    to the held experts, those ``dropped`` over capacity, and those
    computed ``per_expert`` (``(e_loc,)``)."""
    T, d = x.shape
    E, k = spec.n_experts, spec.top_k
    e_loc = wg.shape[0]
    lo = spec.first_expert
    if model_axis:
        lo = lo + jax.lax.axis_index(model_axis) * e_loc

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
    mine = (expert >= lo) & (expert < lo + e_loc)
    if valid is not None:
        mine = mine & jnp.repeat(valid, k)
    cap = _capacity(T, k, n_shards, spec.capacity_slack)
    packed = _compact_ids(mine, cap, spec.dispatch)               # (cap,)
    routed = jnp.sum(mine.astype(jnp.int32))
    pv = jnp.arange(packed.shape[0], dtype=jnp.int32) < routed

    tok = packed // k
    xe = jnp.take(x, tok, axis=0) * pv[:, None].astype(x.dtype)   # (cap, d)
    le = jnp.take(expert, packed) - lo
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
    units = {"routed": routed, "dropped": routed - jnp.sum(gs),
             "per_expert": gs}
    return y, aux, units


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
        y, aux, _ = moe_ffn_local(
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
