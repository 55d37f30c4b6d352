"""Composable decoder-only transformer supporting every assigned family:
dense GQA/MQA, sliding-window:global patterns, qk-norm, MoE FFNs, Mamba
blocks (hybrid), and xLSTM (mLSTM/sLSTM) stacks.

Layer heterogeneity is expressed as a repeating *superblock*: e.g. Jamba is
(mamba x3, attn, mamba x4) with MoE every second layer; Gemma-3 is
(local x5, global). The stack scans over superblocks (keeps HLO compact at
512 devices) with configurable remat.

EARTH touchpoints: fused interleaved gate/up GLU (segment FIELD=2), fused
interleaved KV beats -> interleaved KV cache (segment FIELD=2, one
transaction per token), MoE dispatch via shift-network compaction.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.dist.sharding import ShardCtx
from repro.models import attention, layers
from repro.models.moe import MoESpec, init_moe, moe_layer
from repro.models.ssm import (MambaCache, MambaSpec, init_mamba,
                              init_mamba_cache, mamba_decode_step,
                              mamba_forward)
from repro.models.xlstm import (MLSTMState, SLSTMState, XLSTMSpec, init_mlstm,
                                init_mlstm_state, init_slstm,
                                init_slstm_state, mlstm_decode_step,
                                mlstm_forward, slstm_decode_step,
                                slstm_forward)


@dataclasses.dataclass(frozen=True)
class EncoderSpec:
    n_layers: int
    context: int          # encoder sequence length (e.g. whisper 1500 frames)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                      # 0 -> d_model // n_heads
    block_pattern: tuple = ("attn",)       # kinds per superblock position
    window_pattern: tuple = (None,)        # sliding window per position
    moe_pattern: tuple = (False,)          # MoE FFN per position
    mlp: str = "swiglu"                    # "swiglu" | "mlp" | "none"
    fused_glu: bool = True                 # EARTH interleaved gate/up
    qk_norm: bool = False
    rope_theta: float = 1e4
    moe: MoESpec | None = None
    mamba: MambaSpec | None = None
    xlstm: XLSTMSpec | None = None
    encoder: EncoderSpec | None = None     # whisper
    vlm_patches: int = 0                   # llava stub: # patch embeddings
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    remat: str = "full"                    # "none" | "full" | "dots"
    scan_layers: bool = True
    # EARTH access lowering in-model: an impl string pins it; None defers
    # to vx.Policy.default() (REPRO_VX_IMPL env var, else platform) — ONE
    # knob for the whole stack (see repro/vx/policy.py).
    kernel_impl: str | None = None
    ssm_chunk: int = 128

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def sb_len(self) -> int:
        return len(self.block_pattern)

    @property
    def n_superblocks(self) -> int:
        assert self.n_layers % self.sb_len == 0, (self.name, self.n_layers,
                                                  self.sb_len)
        return self.n_layers // self.sb_len

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def vx_policy(self):
        """The access policy this model lowers through (vx.resolve of
        ``kernel_impl``: a pinned impl, or the ambient policy)."""
        from repro import vx
        return vx.resolve(self.kernel_impl)

    def pos_has_ffn(self, i: int) -> bool:
        kind = self.block_pattern[i]
        if kind in ("mlstm", "slstm"):
            return False
        return bool(self.moe_pattern[i]) or (self.mlp != "none"
                                             and self.d_ff > 0)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_pos(key, cfg: ModelConfig, i: int) -> dict:
    kind = cfg.block_pattern[i]
    keys = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": jnp.ones((cfg.d_model,), cfg.pdtype)}
    if kind == "attn":
        p["attn"] = attention.init_attention(
            keys[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
            qk_norm=cfg.qk_norm, dtype=cfg.pdtype)
    elif kind == "mamba":
        spec = cfg.mamba
        p["mamba"] = init_mamba(keys[0], spec, cfg.pdtype)
        # reshape in_proj for clean (x|z) sharding: (d, 2, ed)
        p["mamba"]["in_proj"] = p["mamba"]["in_proj"].reshape(
            cfg.d_model, 2, spec.ed)
    elif kind == "mlstm":
        p["xl"] = init_mlstm(keys[0], cfg.xlstm, cfg.pdtype)
        p["xl"]["up"] = p["xl"]["up"].reshape(cfg.d_model, 2,
                                              cfg.xlstm.m_inner)
    elif kind == "slstm":
        p["slstm"] = init_slstm(keys[0], cfg.xlstm, cfg.pdtype)
    else:
        raise ValueError(kind)
    if cfg.pos_has_ffn(i):
        p["ln2"] = jnp.ones((cfg.d_model,), cfg.pdtype)
        if cfg.moe_pattern[i]:
            p["moe"] = init_moe(keys[1], cfg.d_model, cfg.moe, cfg.pdtype)
        elif cfg.mlp == "swiglu":
            p["ffn"] = layers.init_glu(keys[1], cfg.d_model, cfg.d_ff,
                                       fused=cfg.fused_glu, dtype=cfg.pdtype)
        else:
            p["mlp"] = layers.init_mlp(keys[1], cfg.d_model, cfg.d_ff,
                                       cfg.pdtype)
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    ke, kb, kh = jax.random.split(key, 3)
    params: dict[str, Any] = {
        "embed": (jax.random.normal(ke, (cfg.vocab, cfg.d_model), cfg.pdtype)
                  * cfg.d_model ** -0.5),
        "final_norm": jnp.ones((cfg.d_model,), cfg.pdtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            kh, (cfg.vocab, cfg.d_model), cfg.pdtype) * cfg.d_model ** -0.5

    def init_sb(k):
        ks = jax.random.split(k, cfg.sb_len)
        return {f"pos{i}": _init_pos(ks[i], cfg, i)
                for i in range(cfg.sb_len)}

    sb_keys = jax.random.split(kb, cfg.n_superblocks)
    sbs = [init_sb(k) for k in sb_keys]
    params["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *sbs)
    if cfg.encoder is not None:
        from repro.models import encdec
        params["encoder"] = encdec.init_encoder(
            jax.random.fold_in(key, 7), cfg)
        params["cross"] = encdec.init_cross_stack(
            jax.random.fold_in(key, 8), cfg)
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Superblock application (train / prefill / decode)
# ---------------------------------------------------------------------------

def _ffn_apply(p, x, cfg: ModelConfig, ctx, i: int, *, policy=None):
    """``policy`` overrides cfg.vx_policy for the GLU field split — the
    step scheduler (core/accessfuse.py) inlines single-token splits on the
    XLA path during fused decode instead of paying a kernel launch."""
    aux = jnp.zeros((), jnp.float32)
    if not cfg.pos_has_ffn(i):
        return x, aux
    h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe_pattern[i]:
        y, aux = moe_layer(p["moe"], h, cfg.moe, ctx)
    elif cfg.mlp == "swiglu":
        y = layers.glu_ffn(p["ffn"], h, fused=cfg.fused_glu,
                           policy=policy if policy is not None
                           else cfg.vx_policy)
    else:
        y = layers.mlp_ffn(p["mlp"], h)
    return x + y, aux


def _attn_apply(p, x, cfg: ModelConfig, ctx, i: int, positions,
                mode: str, cross_kv=None):
    """Returns (x, kv_beat or None)."""
    h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v, kv = attention.qkv_project(
        p["attn"], h, cfg.n_heads, cfg.n_kv_heads, cfg.hd, positions,
        cfg.rope_theta, policy=cfg.vx_policy)
    B, S = x.shape[:2]
    window = cfg.window_pattern[i]
    out = attention.flash_attention(q, k, v, causal=True, window=window,
                                    q_chunk=min(512, S),
                                    kv_chunk=min(512, S), ctx=ctx)
    x = x + out.reshape(B, S, cfg.n_heads * cfg.hd) @ p["attn"]["wo"]
    if cross_kv is not None:
        ck, cv = cross_kv
        x = x + attention.cross_attention(p["cross"], layers.rms_norm(
            x, p["ln_cross"], cfg.norm_eps), ck, cv, cfg.n_heads,
            cfg.n_kv_heads, cfg.hd, ctx=ctx)
    return x, (kv if mode == "prefill" else None)


def superblock_apply(sb_p, x, cfg: ModelConfig, ctx, positions, *,
                     mode: str = "train"):
    """Apply one superblock. Returns (x, aux, cache_updates).

    Each position is independently remat'd (nested checkpoint): during the
    superblock's backward only ONE position's residuals are live — without
    this, wide multi-position superblocks (Jamba: 8) hold every position's
    fp32 intermediates at once (~96 GiB/device measured at 398B scale)."""
    aux_total = jnp.zeros((), jnp.float32)
    updates = {}
    for i, kind in enumerate(cfg.block_pattern):
        fn = functools.partial(_position_apply, cfg=cfg, ctx=ctx, i=i,
                               kind=kind, mode=mode)
        if cfg.remat != "none" and len(cfg.block_pattern) > 1:
            fn = jax.checkpoint(fn, static_argnums=())
        x, aux, upd = fn(sb_p[f"pos{i}"], x, positions)
        aux_total = aux_total + aux
        if upd is not None:
            updates[f"pos{i}"] = upd
        if ctx is not None and ctx.mesh is not None:
            # Megatron-SP: residual stream sequence-sharded over the model
            # axis between blocks (activation memory / model_size)
            seq_ax = (ctx.model_if_divisible(x.shape[1])
                      if ctx.seq_shard_acts else None)
            x = ctx.constrain(x, ctx.batch_spec(seq_ax, None))
    return x, aux_total, updates


def _position_apply(p, x, positions, *, cfg: ModelConfig, ctx, i: int,
                    kind: str, mode: str):
    """One (mixer + FFN) position of a superblock."""
    update = None
    if kind == "attn":
        x, kv = _attn_apply(p, x, cfg, ctx, i, positions, mode)
        if mode == "prefill":
            update = _ring_trim(kv, cfg.window_pattern[i])
    elif kind == "mamba":
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        pm = dict(p["mamba"])
        pm["in_proj"] = pm["in_proj"].reshape(cfg.d_model, 2 * cfg.mamba.ed)
        y, state = mamba_forward(pm, h, cfg.mamba, chunk=cfg.ssm_chunk)
        x = x + y
        if mode == "prefill":
            update = state
    elif kind == "mlstm":
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        px = dict(p["xl"])
        px["up"] = px["up"].reshape(cfg.d_model, 2 * cfg.xlstm.m_inner)
        y, state = mlstm_forward(px, h, cfg.xlstm)
        x = x + y
        if mode == "prefill":
            update = state
    elif kind == "slstm":
        h = layers.rms_norm(x, p["ln1"], cfg.norm_eps)
        y, state = slstm_forward(p["slstm"], h, cfg.xlstm)
        x = x + y
        if mode == "prefill":
            update = state
    else:
        raise ValueError(kind)
    aux = jnp.zeros((), jnp.float32)
    if cfg.pos_has_ffn(i):
        x, aux = _ffn_apply(p, x, cfg, ctx, i)
    return x, aux, update


def _ring_trim(kv: jax.Array, window: int | None) -> jax.Array:
    """Prefill cache beat tensor; windowed layers keep a ring of size W."""
    B, S = kv.shape[:2]
    if window is None or S <= window:
        return kv
    last = kv[:, -window:]
    return jnp.roll(last, shift=S % window, axis=1)


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def cast_params(params, cfg: ModelConfig, ctx=None):
    """Mixed precision: compute in cfg.compute_dtype, master in param_dtype.

    Router weights stay fp32 (numerically sensitive softmax logits).
    When ctx is given, each bf16 copy is pinned to the SAME sharding as its
    fp32 master — otherwise XLA may all-gather FSDP-sharded weights in fp32
    and convert after (2x wire bytes + fp32 gathered buffers; measured)."""
    if cfg.cdtype == cfg.pdtype:
        return params
    specs = None
    if ctx is not None and ctx.mesh is not None:
        from repro.dist.sharding import tree_param_specs
        specs = tree_param_specs(params, ctx)
    flat = jax.tree_util.tree_flatten_with_path(params)
    spec_leaves = (jax.tree_util.tree_flatten(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))[0]
        if specs is not None else [None] * len(flat[0]))
    leaves = []
    for (kp, leaf), spec in zip(flat[0], spec_leaves):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        if jnp.issubdtype(leaf.dtype, jnp.floating) and \
                not path.endswith("router"):
            leaf = leaf.astype(cfg.cdtype)
            if spec is not None:
                leaf = ctx.constrain(leaf, spec)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(flat[1], leaves)


def _embed_inputs(params, batch, cfg: ModelConfig, ctx) -> jax.Array:
    x = layers.embed(batch["tokens"], params["embed"]).astype(cfg.cdtype)
    if cfg.vlm_patches and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(cfg.cdtype)
        x = jax.lax.dynamic_update_slice(x, pe, (0, 0, 0))
    if ctx is not None and ctx.mesh is not None:
        seq_ax = (ctx.model_if_divisible(x.shape[1])
                  if ctx.seq_shard_acts else None)
        x = ctx.constrain(x, ctx.batch_spec(seq_ax, None))
    return x


def forward(params, batch, cfg: ModelConfig, ctx: ShardCtx | None,
            *, mode: str = "train"):
    """batch: {"tokens": (B,S) int32, optional "patch_embeds"}.

    Returns (logits (B,S,V), aux, cache_states)."""
    # serve paths keep their (possibly 2D fsdp) weight placement; only the
    # train path pins bf16 copies to the master sharding
    params = cast_params(params, cfg, ctx if mode == "train" else None)
    if cfg.encoder is not None:
        from repro.models import encdec
        return encdec.forward(params, batch, cfg, ctx, mode=mode)
    x = _embed_inputs(params, batch, cfg, ctx)
    B, S = batch["tokens"].shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def sb_fn(carry, sb_p):
        x, aux = carry
        x, aux_d, upd = superblock_apply(sb_p, x, cfg, ctx, positions,
                                         mode=mode)
        return (x, aux + aux_d), upd

    body = sb_fn
    if cfg.remat == "full":
        body = jax.checkpoint(sb_fn)
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            sb_fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    if cfg.scan_layers:
        (x, aux), cache_states = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    else:
        aux = jnp.zeros((), jnp.float32)
        cache_states = []
        for sbi in range(cfg.n_superblocks):
            sb_p = jax.tree.map(lambda a: a[sbi], params["blocks"])
            (x, aux), upd = body((x, aux), sb_p)
            cache_states.append(upd)
        if mode == "prefill" and cache_states and cache_states[0]:
            cache_states = jax.tree.map(lambda *xs: jnp.stack(xs),
                                        *cache_states)

    if mode == "prefill":
        x = x[:, -1:]  # serving prefill only needs next-token logits
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if mode == "hidden":
        return x, aux, cache_states
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = layers.unembed(x, head.astype(cfg.cdtype))
    if ctx is not None and ctx.mesh is not None:
        logits = ctx.constrain(
            logits, ctx.batch_spec(None, ctx.model_if_divisible(cfg.vocab)))
    return logits, aux, cache_states


def label_logprob_terms(logits: jax.Array, labels: jax.Array):
    """(lse, ll) computed WITHOUT gathering over the (model-sharded) vocab
    axis: reductions partition cleanly (partial + all-reduce); a
    take_along_axis here would force an all-gather of full-vocab fp32
    logits (~13 GiB/device at granite scale — measured)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = labels[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1)
    ll = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    return lse, ll


def chunked_xent(x, head, labels, w, ctx, *, chunk: int = 512):
    """Head matmul + cross entropy, scanned over sequence chunks with remat.

    Full-sequence logits at 262k vocab are multi-GiB fp32 per device; the
    chunked form keeps only (B, chunk, V) alive (recomputed in backward)."""
    B, S, _ = x.shape
    if S % chunk or S <= chunk:
        chunk = S
    nc = S // chunk

    @jax.checkpoint
    def body(carry, i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk, 1)
        logits = layers.unembed(sl(x), head)
        if ctx is not None and ctx.mesh is not None:
            logits = ctx.constrain(logits, ctx.batch_spec(
                None, ctx.model_if_divisible(head.shape[0])))
        lse, ll = label_logprob_terms(logits, sl(labels))
        ws = sl(w)
        num, den = carry
        return (num + jnp.sum((lse - ll) * ws), den + jnp.sum(ws)), None

    (num, den), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        jnp.arange(nc))
    return num / jnp.maximum(den, 1.0)


def loss_fn(params, batch, cfg: ModelConfig, ctx: ShardCtx | None):
    """Next-token cross entropy; batch: tokens, labels, loss_weight."""
    x, aux, _ = forward(params, batch, cfg, ctx, mode="hidden")
    labels = batch["labels"]
    w = batch.get("loss_weight")
    if w is None:
        w = jnp.ones(labels.shape, jnp.float32)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    loss = chunked_xent(x, head.astype(cfg.cdtype), labels, w, ctx)
    return loss + aux, {"loss": loss, "aux": aux}
