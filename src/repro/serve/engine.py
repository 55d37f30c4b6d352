"""Serving engine: jit'd prefill / decode steps with cache shardings,
plus a simple continuous-batching session manager.

Cache sharding policy (see DESIGN.md §6):
  * decode_32k (B=128): batch over data axes, KV heads over model when
    divisible, else sequence over model.
  * long_500k (B=1): batch cannot shard — the KV sequence axis is sharded
    over (data, model) (sequence parallelism). Distributed softmax over the
    sharded axis is handled by XLA SPMD (max/sum all-reduces); the
    shard_map log-sum-exp merge is the §Perf optimization.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import ShardCtx, tree_param_specs
from repro.models import decode as dec
from repro.models import encdec
from repro.models.transformer import ModelConfig, forward


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int
    cache_dtype: str = "bfloat16"
    long_context: bool = False     # sequence-parallel KV sharding
    # Whole-step access fusion (core/accessfuse.py): one fused KV split
    # per decode step.  Costs one transient cache-sized pre-split copy
    # (k_pre/v_pre live across the step, ~+1x KV memory at peak); set
    # False when the cache is the memory ceiling.  Applies to long_context
    # too (PR 4): seq-parallel caches split SHARD-LOCALLY through the vx
    # sharding-aware lowering instead of being sliced globally.
    step_fusion: bool = True


def cache_specs(cfg: ModelConfig, ctx: ShardCtx, scfg: ServeConfig,
                cache_template: Any):
    """PartitionSpec pytree for the cache."""
    if ctx.mesh is None:
        return None

    batch_axes = ctx.data_axes if ctx.data_axes else None

    def _seq_divisible(s: int, axes) -> bool:
        n = 1
        for a in (axes if isinstance(axes, tuple) else (axes,)):
            n *= ctx.mesh.shape[a]
        return s % n == 0

    def spec_for(path: str, leaf) -> P:
        if path.endswith("len"):
            return P()
        if scfg.long_context and leaf.ndim == 5:
            # (NS, B, S, K, 2D): B=1 — shard the sequence over every axis
            axes = ctx.seq_axes or (ctx.data_axes
                                    + ((ctx.model_axis,)
                                       if ctx.model_axis else ()))
            if axes and _seq_divisible(leaf.shape[2], axes):
                return P(None, None, axes)
            return P()
        if leaf.ndim == 5:  # (NS, B, S, K, 2D)
            k, s = leaf.shape[3], leaf.shape[2]
            if ctx.model_axis and k % ctx.model_size == 0:
                return P(None, batch_axes, None, ctx.model_axis)
            if ctx.model_axis and s % ctx.model_size == 0:
                return P(None, batch_axes, ctx.model_axis)  # seq-sharded
            return P(None, batch_axes)
        return _state_spec(leaf)

    def _state_spec(leaf) -> P:
        # (NS, B, inner...) recurrent state: batch over data, biggest inner
        # dim over model if divisible
        parts = [None, batch_axes] + [None] * (leaf.ndim - 2)
        if ctx.model_axis:
            for i in range(2, leaf.ndim):
                if leaf.shape[i] % ctx.model_size == 0 and \
                        leaf.shape[i] >= ctx.model_size:
                    parts[i] = ctx.model_axis
                    break
        return P(*parts)

    flat = jax.tree_util.tree_flatten_with_path(cache_template)[0]
    specs = []
    for kp, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        specs.append(spec_for(path, leaf))
    treedef = jax.tree_util.tree_structure(cache_template)
    return jax.tree_util.tree_unflatten(treedef, specs)


def serve_param_shardings(params_template, cfg: ModelConfig, ctx: ShardCtx):
    if ctx.mesh is None:
        return None
    specs = tree_param_specs(params_template, ctx)
    return jax.tree.map(lambda s: ctx.sharding(s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def jit_decode_step(cfg: ModelConfig, ctx: ShardCtx, scfg: ServeConfig,
                    params_template, cache_template, *, param_ctx=None):
    """serve_step(params, cache, token) -> (logits, cache), fully sharded.

    ``param_ctx``: optional separate ShardCtx for WEIGHT placement — huge
    models (Jamba-398B) shard weights 2D over (data x model) even though
    the serving batch only uses the model axis (weights are gathered
    layer-by-layer under the superblock scan)."""
    from repro import vx
    # one-time host compile of the FIELD=2 segment plans the fused KV
    # split consults (decode takes no runtime-stride path: skip those).
    # Resolved through the model's policy so prewarming compiles exactly
    # the plans the serve path will hit (nothing under impl="ref").
    vx.warm(2 * cfg.hd, strided=False, fields=(2,), policy=cfg.vx_policy)

    if cfg.encoder is not None:
        def serve_step(params, cache, token):
            return encdec.decode_step(params, cache, token, cfg, ctx)
    else:
        # Step fusion holds for long_500k too (PR 4): the seq-sharded
        # cache leaves are annotated with their placement and the fused
        # FIELD=2 split lowers shard-locally under shard_map (offset
        # space is untouched — the lane permutation is elementwise over
        # the sequence), so SPMD never rematerializes the pre-split
        # leaves the way the old global slice did.
        fuse = scfg.step_fusion
        # axis=-3: the sequence dim of the (NS, B, Sc, K, 2D) leaves,
        # counted from the end (stack-stable)
        kv_shard = ctx.vx_seq_shard(-3) if scfg.long_context else None

        def serve_step(params, cache, token):
            # one fused append/split for all layers per decode step
            return dec.decode_step(params, cache, token, cfg, ctx,
                                   fuse=fuse, kv_shard=kv_shard)

    if ctx.mesh is None:
        return jax.jit(serve_step, donate_argnums=1)
    psh = serve_param_shardings(params_template, cfg, param_ctx or ctx)
    cspecs = cache_specs(cfg, ctx, scfg, cache_template)
    csh = jax.tree.map(lambda s: ctx.sharding(s), cspecs,
                       is_leaf=lambda x: isinstance(x, P))
    tsh = ctx.sharding(ctx.batch_spec())
    osh = (ctx.sharding(ctx.batch_spec(ctx.model_if_divisible(cfg.vocab))),
           csh)
    return jax.jit(serve_step, in_shardings=(psh, csh, tsh),
                   out_shardings=osh, donate_argnums=1)


def jit_prefill(cfg: ModelConfig, ctx: ShardCtx, params_template,
                batch_template, *, param_ctx=None):
    def prefill(params, batch):
        logits, _, cache_states = forward(params, batch, cfg, ctx,
                                          mode="prefill")
        return logits, cache_states

    if ctx.mesh is None:
        return jax.jit(prefill)
    psh = serve_param_shardings(params_template, cfg, param_ctx or ctx)
    bsh = jax.tree.map(
        lambda x: ctx.sharding(ctx.batch_spec(*([None] * (x.ndim - 1)))),
        batch_template)
    return jax.jit(prefill, in_shardings=(psh, bsh))


# ---------------------------------------------------------------------------
# Compat wrapper: the old fixed-slot dense server API over the paged
# runtime (serve/scheduler.py + serve/paged_cache.py).
# ---------------------------------------------------------------------------

class BatchedServer:
    """Fixed-slot continuous batching — thin wrapper over
    :class:`repro.serve.scheduler.Scheduler`.

    Since PR 5 the backing runtime is the PAGED cache: per-slot position
    vectors, a shared page pool per layer, device free-list reclamation on
    ``finish`` (a reused slot can never attend to the previous occupant's
    cache — the old dense server left stale KV and a shared position
    counter behind), multi-token prompts through the jit'd prefill, and
    optional temperature / top-k sampling.  The old single-token
    ``add_request(int)`` / ``step()`` / ``finish()`` surface is unchanged;
    ``cache`` is presented in the legacy ``{"len", "blocks"}`` shape with
    ``len`` = the furthest active position.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int, max_len: int,
                 ctx: ShardCtx | None = None, cache_dtype=jnp.float32,
                 page_size: int | None = None,
                 num_pages: int | None = None, temperature: float = 0.0,
                 top_k: int | None = None, seed: int = 0, **lifecycle_kw):
        from repro.serve.scheduler import Scheduler
        self.cfg, self.params = cfg, params
        self.slots, self.max_len, self.ctx = slots, max_len, ctx
        # lifecycle_kw passes the hardened-runtime knobs through
        # unchanged (queue_depth / preemption / guard_nan / watchdog /
        # debug_invariants / clock, the PR 8 prefix_cache / chunk_pages
        # prefill knobs, and the PR 9 kv_quant quantized-pool selector —
        # see serve/scheduler.py)
        self.scheduler = Scheduler(
            cfg, params, slots=slots, max_len=max_len, page_size=page_size,
            num_pages=num_pages, cache_dtype=cache_dtype,
            temperature=temperature, top_k=top_k,
            seed=seed, **lifecycle_kw)

    @property
    def active(self) -> list:
        return self.scheduler.active

    @property
    def tokens(self) -> list:
        return self.scheduler.tokens

    @property
    def cache(self) -> dict:
        st = self.scheduler.cache.state
        return {"len": jnp.max(st["pos"]), "blocks": st["blocks"]}

    def add_request(self, prompt_token=None, *, prompt=None) -> int:
        """Admit a request: a single first token (legacy form) or a full
        prompt list (prefilled through ``jit_prefill``)."""
        req = prompt if prompt is not None else prompt_token
        if req is None:
            raise ValueError("pass a prompt token or prompt= list")
        return self.scheduler.add_request(req)

    def step(self) -> list[int]:
        """Advance every active slot one token."""
        return self.scheduler.step()

    def submit(self, prompt, **kw):
        """Queue a typed request (lifecycle surface — see Scheduler)."""
        return self.scheduler.submit(prompt, **kw)

    def tick(self):
        """One lifecycle iteration: admit / step / retire."""
        return self.scheduler.tick()

    def finish(self, slot: int) -> list[int]:
        """Release the slot (pages reclaimed, per-slot state cleared)."""
        return self.scheduler.finish(slot)


def make_fleet(cfg: ModelConfig, params, *, replicas: int, slots: int,
               max_len: int, **fleet_kw):
    """N scheduler replicas behind the health-checked fleet router —
    the multi-replica counterpart of :class:`BatchedServer`.  Each
    replica is the full PR 6 hardened runtime (own page pool, own
    admission queue) over ONE shared params pytree; the router does
    least-loaded admission, heartbeat/watchdog health tracking, and
    replay-based failover (serve/fleet.py, DESIGN.md §8.2).  A
    ``replicas=1`` fleet is exactly one ``BatchedServer.scheduler``
    behind a router."""
    from repro.serve.fleet import FleetRouter
    return FleetRouter(cfg, params, replicas=replicas, slots=slots,
                       max_len=max_len, **fleet_kw)
