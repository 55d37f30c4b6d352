"""GPipe pipeline parallelism over one mesh axis (the multi-pod "pod" axis).

The superblock stack is split into ``mesh.shape[axis]`` contiguous stages;
microbatches stream through the stages with activations handed forward by
``lax.ppermute`` (whose transpose carries gradients backward, so a plain
``jax.grad`` through ``pipeline_loss_fn`` trains correctly).

The schedule is the classic GPipe fill/steady/drain loop: with M
microbatches and S stages, tick t has stage s working on microbatch
``t - s`` (when in range). Every device executes the identical program
(SPMD); out-of-range ticks compute on don't-care data and are masked out of
the loss accumulators, which keeps the body shard_map-uniform.

Numerics match ``models.transformer.loss_fn`` (same per-token terms,
microbatch-partitioned sums combined before the division), verified to
rtol 2e-3 by tests/_dist_checks.py::check_pipeline_equivalence.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import ShardCtx


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    axis: str = "pod"
    n_microbatches: int = 2


def _stage_blocks(blocks, stage, per: int):
    """Slice this stage's ``per`` superblocks out of the (NS, ...) stacks."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, stage * per, per, 0),
        blocks)


def pipeline_loss_fn(params, batch, cfg, ctx: ShardCtx,
                     pcfg: PipelineConfig):
    """Pipelined equivalent of ``loss_fn(params, batch, cfg, None)``.

    params/batch enter replicated; the pipeline axis is used for stage
    placement and activation hand-off only. Returns (loss + aux, metrics).
    """
    from repro.models import layers
    from repro.models.transformer import (cast_params, label_logprob_terms,
                                          superblock_apply)
    assert ctx.mesh is not None, "pipeline parallelism needs a mesh"
    n_stages = ctx.mesh.shape[pcfg.axis]
    M = pcfg.n_microbatches
    NS = cfg.n_superblocks
    assert NS % n_stages == 0, (NS, n_stages)
    per = NS // n_stages
    B = batch["tokens"].shape[0]
    assert B % M == 0, (B, M)

    def body(params, batch):
        stage = jax.lax.axis_index(pcfg.axis)
        cparams = cast_params(params, cfg, None)
        mb = jax.tree.map(
            lambda x: x.reshape((M, B // M) + x.shape[1:]), batch)
        bm, S = B // M, batch["tokens"].shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), (bm, S))
        my_blocks = _stage_blocks(cparams["blocks"], stage, per)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        head = head.astype(cfg.cdtype)

        def stage_apply(x):
            def sb_fn(x, sb_p):
                x, aux_d, _ = superblock_apply(sb_p, x, cfg, None, positions,
                                               mode="train")
                return x, aux_d
            x, auxs = jax.lax.scan(sb_fn, x, my_blocks)
            return x, jnp.sum(auxs)

        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        is_last = (stage == n_stages - 1).astype(jnp.float32)
        buf = jnp.zeros((bm, S, cfg.d_model), cfg.cdtype)
        num = den = aux_sum = jnp.zeros((), jnp.float32)
        for t in range(M + n_stages - 1):
            m_in = min(t, M - 1)
            x0 = layers.embed(mb["tokens"][m_in],
                              cparams["embed"]).astype(cfg.cdtype)
            x_in = jnp.where(stage == 0, x0, buf)
            y, aux_t = stage_apply(x_in)
            m_out = t - (n_stages - 1)
            if 0 <= m_out < M:
                h = layers.rms_norm(y, cparams["final_norm"], cfg.norm_eps)
                logits = layers.unembed(h, head)
                lse, ll = label_logprob_terms(logits, mb["labels"][m_out])
                w = mb.get("loss_weight")
                w = (jnp.ones((bm, S), jnp.float32) if w is None
                     else w[m_out].astype(jnp.float32))
                num = num + jnp.sum((lse - ll) * w) * is_last
                den = den + jnp.sum(w) * is_last
            # every stage contributes its superblocks' aux once per REAL
            # microbatch it processed (ticks stage..stage+M-1)
            in_range = jnp.logical_and(t - stage >= 0, t - stage < M)
            aux_sum = aux_sum + aux_t * in_range.astype(jnp.float32)
            buf = jax.lax.ppermute(y, pcfg.axis, perm=fwd)
        num = jax.lax.psum(num, pcfg.axis)
        den = jax.lax.psum(den, pcfg.axis)
        aux = jax.lax.psum(aux_sum, pcfg.axis) / M
        loss = num / jnp.maximum(den, 1.0)
        return loss + aux, {"loss": loss, "aux": aux}

    sm = jax.shard_map(body, mesh=ctx.mesh,
                       in_specs=(P(), P()), out_specs=(P(), P()),
                       check_vma=False)
    return sm(params, batch)
