"""Distributed-semantics checks, run in a subprocess with 8 fake devices.

Invoked by tests/test_dist_8dev.py as:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m tests._dist_checks <check_name>
Each check prints CHECK_OK on success.
"""
from __future__ import annotations

import os
import re
import sys

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def check_moe_ep_equivalence():
    """Expert-parallel MoE on a (2,4) mesh == single-device MoE."""
    from repro.dist.sharding import ShardCtx
    from repro.launch.mesh import make_test_mesh
    from repro.models.moe import MoESpec, init_moe, moe_layer

    d = 64
    spec = MoESpec(n_experts=8, top_k=2, d_ff=96, capacity_slack=8.0)
    params = init_moe(jax.random.key(0), d, spec, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (4, 16, d))
    y_local, aux_local = jax.jit(
        lambda p, x: moe_layer(p, x, spec, None))(params, x)
    mesh = make_test_mesh((2, 4), ("data", "model"))
    ctx = ShardCtx(mesh=mesh, data_axes=("data",), model_axis="model")
    y_ep, aux_ep = jax.jit(
        lambda p, x: moe_layer(p, x, spec, ctx))(params, x)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_ep),
                               rtol=2e-4, atol=2e-4)
    # aux is a per-data-shard load-balance estimator averaged with pmean;
    # it is nonlinear in the token partition, so only approximately equal
    np.testing.assert_allclose(float(aux_local), float(aux_ep), rtol=0.1)
    print("CHECK_OK")


def check_sharded_train_step():
    """Sharded train step on (2,4): finite loss, state keeps shardings."""
    from repro.configs import get_arch
    from repro.configs.base import train_batch
    from repro.launch.mesh import make_ctx, make_test_mesh
    from repro.train.step import TrainConfig, init_full_state, jit_train_step

    arch = get_arch("qwen3-0.6b")
    import dataclasses
    cfg = dataclasses.replace(arch.smoke, compute_dtype="bfloat16")
    mesh = make_test_mesh((2, 4), ("data", "model"))
    ctx = make_ctx(mesh)
    tcfg = TrainConfig()
    state = init_full_state(cfg, tcfg, jax.random.key(0))
    batch = train_batch(cfg, 64, 4, specs=False)
    step = jit_train_step(cfg, tcfg, ctx, state, batch)
    state, m1 = step(state, batch)
    state, m2 = step(state, batch)
    assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) < float(m1["loss"]) + 0.5
    # a model-sharded leaf should really be distributed
    wq = state["params"]["blocks"]["pos0"]["attn"]["wq"]
    assert len(wq.sharding.device_set) == 8 or not wq.sharding.is_fully_replicated
    print("CHECK_OK")


def check_pipeline_equivalence():
    """GPipe over pod axis == plain forward (loss equality)."""
    import dataclasses
    from repro.configs import get_arch
    from repro.configs.base import train_batch
    from repro.dist.pipeline_par import PipelineConfig, pipeline_loss_fn
    from repro.launch.mesh import make_ctx
    from repro.models.transformer import loss_fn

    cfg = get_arch("qwen3-0.6b").smoke  # 2 layers -> 2 stages x 1
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
    ctx = make_ctx(mesh)
    from repro.models.transformer import init_params
    params = init_params(cfg, jax.random.key(0))
    batch = train_batch(cfg, 32, 4, specs=False)
    l_ref, _ = jax.jit(lambda p, b: loss_fn(p, b, cfg, None))(params, batch)
    pcfg = PipelineConfig(axis="pod", n_microbatches=2)
    l_pp, _ = jax.jit(lambda p, b: pipeline_loss_fn(p, b, cfg, ctx, pcfg))(
        params, batch)
    np.testing.assert_allclose(float(l_ref), float(l_pp), rtol=2e-3)
    # gradients flow through ppermute
    g = jax.jit(jax.grad(lambda p, b: pipeline_loss_fn(
        p, b, cfg, ctx, pcfg)[0]))(params, batch)
    gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
    print("CHECK_OK")


def check_elastic_reshard():
    """Checkpoint from a (2,4) mesh restores onto (4,2)."""
    import tempfile
    from repro.dist.sharding import ShardCtx
    from repro.ft.checkpoint import CheckpointManager
    from repro.ft.elastic import restore_elastic
    from repro.launch.mesh import make_test_mesh

    tree = {"blocks": {"pos0": {"attn": {
        "wq": jax.random.normal(jax.random.key(0), (4, 64, 64))}}},
        "embed": jax.random.normal(jax.random.key(1), (128, 64))}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, tree, blocking=True)
        mesh2 = make_test_mesh((4, 2), ("data", "model"))
        ctx2 = ShardCtx(mesh=mesh2, data_axes=("data",), model_axis="model")
        restored, _ = restore_elastic(mgr, tree, ctx2)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b)), tree, restored)
        wq = restored["blocks"]["pos0"]["attn"]["wq"]
        assert wq.sharding.mesh.shape["model"] == 2
    print("CHECK_OK")


def check_seq_parallel_decode():
    """Decode with KV cache sharded over the sequence axis == unsharded."""
    import dataclasses
    from repro.configs import get_arch
    from repro.configs.base import decode_inputs
    from repro.launch.mesh import make_ctx, make_test_mesh
    from repro.models import decode as dec
    from repro.models.transformer import init_params
    from repro.serve.engine import ServeConfig, jit_decode_step

    cfg = get_arch("qwen3-0.6b").smoke
    params = init_params(cfg, jax.random.key(0))
    cache, token = decode_inputs(cfg, seq=32, batch=8, specs=False,
                                 cache_dtype=jnp.float32)
    cache["len"] = jnp.asarray(16, jnp.int32)
    # fill cache with noise so attention actually reads it
    cache["blocks"] = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(2), a.shape, a.dtype)
        if a.dtype != jnp.int32 else a, cache["blocks"])
    logits_ref, _ = jax.jit(
        lambda p, c, t: dec.decode_step(p, c, t, cfg, None))(
            params, cache, token)
    mesh = make_test_mesh((2, 4), ("data", "model"))
    ctx = make_ctx(mesh, long_context=True)
    scfg = ServeConfig(max_len=32, long_context=True)
    step = jit_decode_step(cfg, ctx, scfg, params, cache)
    logits_sp, _ = step(params, dict(cache), token)
    np.testing.assert_allclose(np.asarray(logits_ref, np.float32),
                               np.asarray(logits_sp, np.float32),
                               rtol=3e-3, atol=3e-3)
    print("CHECK_OK")


def _longctx_setup(seq=32, batch=8):
    import dataclasses
    from repro.configs import get_arch
    from repro.configs.base import decode_inputs
    from repro.models.transformer import init_params

    cfg = get_arch("qwen3-0.6b").smoke
    params = init_params(cfg, jax.random.key(0))

    def fresh_cache():
        cache, token = decode_inputs(cfg, seq=seq, batch=batch, specs=False,
                                     cache_dtype=jnp.float32)
        cache["len"] = jnp.asarray(seq // 2, jnp.int32)
        cache["blocks"] = jax.tree.map(
            lambda a: jax.random.normal(jax.random.key(2), a.shape, a.dtype)
            if a.dtype != jnp.int32 else a, cache["blocks"])
        return cache, token

    return cfg, params, fresh_cache


def check_longctx_fused_decode():
    """PR 4 headline: the seq-sharded long-context decode step runs WITH
    step fusion — bit-exact vs the per-access oracle on the same
    placement, close to the unsharded oracle, and the fused path
    introduces no cache-sized all-gather (the old involuntary SPMD
    rematerialization)."""
    import re
    from repro.launch.mesh import make_ctx, make_test_mesh
    from repro.models import decode as dec
    from repro.serve.engine import ServeConfig, jit_decode_step

    cfg, params, fresh_cache = _longctx_setup()
    cache, token = fresh_cache()
    logits_ref, _ = jax.jit(
        lambda p, c, t: dec.decode_step(p, c, t, cfg, None, fuse=False))(
            params, cache, token)

    mesh = make_test_mesh((2, 4), ("data", "model"))
    ctx = make_ctx(mesh, long_context=True)
    texts, logits, caches = {}, {}, {}
    for fuse in (True, False):
        scfg = ServeConfig(max_len=32, long_context=True, step_fusion=fuse)
        cache, token = fresh_cache()
        step = jit_decode_step(cfg, ctx, scfg, params, cache)
        texts[fuse] = step.lower(params, cache, token).compile().as_text()
        logits[fuse], caches[fuse] = step(params, cache, token)

    np.testing.assert_array_equal(np.asarray(logits[True]),
                                  np.asarray(logits[False]))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), caches[True], caches[False])
    np.testing.assert_allclose(np.asarray(logits[True], np.float32),
                               np.asarray(logits_ref, np.float32),
                               rtol=3e-3, atol=3e-3)

    # No involuntary full-cache rematerialization: fusion must not add
    # all-gathers, and none of the fused step's all-gathers may span a
    # full KV-cache leaf (global slice of the seq-sharded pre-split
    # leaves — the exact failure mode that forced per-access before).
    leaf_elems = {int(np.prod(a.shape))
                  for a in jax.tree.leaves(fresh_cache()[0]["blocks"])}
    for name, txt in (("fused", texts[True]), ("per", texts[False])):
        ag = [np.prod([int(d) for d in dims.split(",") if d])
              for dims in re.findall(r"\S+\[([\d,]*)\][^\n]*all-gather",
                                     txt)]
        big = [int(e) for e in ag if e in leaf_elems]
        assert not big, (name, big)
    assert texts[True].count("all-gather") <= texts[False].count(
        "all-gather")
    print("CHECK_OK")


def check_longctx_launch_gate():
    """Sharded mirror of tests/test_step_fusion.py's jaxpr-level gate:
    the seq-sharded fused decode step must issue >= 2x fewer kernel
    launches AND mask operands than the sharded per-access path (counts
    include shard_map bodies)."""
    from repro import vx
    from repro.core import accessfuse
    from repro.launch.mesh import make_ctx, make_test_mesh
    from repro.models import decode as dec

    cfg, params, fresh_cache = _longctx_setup()
    cache, token = fresh_cache()
    mesh = make_test_mesh((2, 4), ("data", "model"))
    ctx = make_ctx(mesh, long_context=True)
    shard = ctx.vx_seq_shard(-3)
    assert shard is not None and shard.nshards == 8

    def fused(p, c, t):
        return dec.decode_step(p, c, t, cfg, ctx, fuse=True,
                               kv_shard=shard)

    def per_access(p, c, t):
        return dec.decode_step(p, c, t, cfg, ctx, fuse=False)

    with vx.use("pallas"), accessfuse.pinned_kernel_lowering():
        lf, mf = accessfuse.jaxpr_access_counts(fused, params, cache, token)
    with vx.use("pallas"):
        lp, mp = accessfuse.jaxpr_access_counts(per_access, params, cache,
                                                token)
    assert lf >= 1 and mf >= 1, (lf, mf)
    assert 2 * lf <= lp, (lf, lp)
    assert 2 * mf <= mp, (mf, mp)
    print("CHECK_OK")


def check_sharded_vx_property():
    """Property sweep: shard-local gather/scatter/transpose match the
    unsharded oracle bit-exactly across layouts (1- and 2-axis meshes),
    strides of either sign, offsets, and field counts."""
    from repro import vx
    from repro.launch.mesh import make_test_mesh

    rng = np.random.default_rng(0)
    layouts = [((8,), ("s",)), ((2, 4), ("a", "b")), ((4, 2), ("a", "b"))]
    for shape, axes in layouts:
        mesh = make_test_mesh(shape, axes)
        lane = vx.Shard(axes=axes, axis=-1, mesh=mesh)
        outer = vx.Shard(axes=axes, axis=-2, mesh=mesh)
        n = 64
        w = jnp.asarray(rng.normal(size=(3, n)), jnp.float32)
        for stride, offset in [(1, 0), (2, 3), (3, 1), (5, 2), (7, 1),
                               (-1, 63), (-2, 50), (-4, 40)]:
            vl = 8
            spec = vx.Strided(n=n, stride=stride, offset=offset, vl=vl)
            want = vx.gather(spec, w, policy="ref")
            got = jax.jit(lambda x: vx.gather(spec, x, policy="ref",
                                              shard=lane))(w)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            vals = jnp.asarray(rng.normal(size=(3, vl)), jnp.float32)
            want_s = vx.scatter(spec, w, vals, policy="ref")
            got_s = jax.jit(lambda x, v: vx.scatter(spec, x, v,
                                                    policy="ref",
                                                    shard=lane))(w, vals)
            np.testing.assert_array_equal(np.asarray(got_s),
                                          np.asarray(want_s))
        for fields in (2, 4):
            aos = jnp.asarray(rng.normal(size=(2, 16, 8 * fields)),
                              jnp.float32)
            spec = vx.Segment(n=8 * fields, fields=fields)
            want = vx.transpose(spec, aos, policy="ref")
            got = jax.jit(lambda x: vx.transpose(spec, x, policy="ref",
                                                 shard=outer))(aos)
            for g, ww in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g),
                                              np.asarray(ww))
            back = jax.jit(lambda parts: vx.transpose(
                spec, list(parts), policy="ref", shard=outer))(tuple(got))
            np.testing.assert_array_equal(np.asarray(back), np.asarray(aos))
    print("CHECK_OK")


def check_paged_pool_shard():
    """Sharded paged-pool gathers: the pool sharded on its page axis over
    1- and 2-axis meshes, gathered shard-locally (owned page block only,
    one psum merge) — bit-exact vs the replicated lowering, for full,
    partial, and unallocated tables, fused multi-pool form included.
    Also: the compiled HLO of the sharded gather contains no all-gather
    of a pool-sized operand (the no-global-slice invariant); and the full
    serving path — paged_decode_step with the pool sharded via
    ShardCtx.vx_pool_shard(-4) — is bit-exact vs the replicated step."""
    from repro import vx
    from repro.dist.sharding import ShardCtx
    from repro.launch.mesh import make_test_mesh
    from repro.models import decode as dec
    from repro.models.transformer import ModelConfig, init_params

    rng = np.random.default_rng(0)
    ps, pages, P, K, D2 = 4, 6, 16, 2, 8
    pool = jnp.asarray(rng.normal(size=(2, P, ps, K, D2)), jnp.float32)
    spec = vx.Paged(page_size=ps, pages=pages, trail=2)
    tables = np.full((3, pages), -1, np.int32)
    tables[0, :pages] = rng.permutation(P)[:pages]        # full
    tables[1, :3] = [15, 0, 7]                            # partial
    table = jnp.asarray(tables)
    want = vx.gather(spec, pool, table=table, policy="ref")

    for shape, axes in [((8,), ("s",)), ((2, 4), ("a", "b")),
                        ((4, 2), ("a", "b"))]:
        mesh = make_test_mesh(shape, axes)
        shard = vx.Shard(axes=axes, axis=-4, mesh=mesh)
        got = jax.jit(lambda pl, tb: vx.gather(
            spec, pl, table=tb, policy="ref", shard=shard))(pool, table)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        outs = jax.jit(lambda pl, tb: vx.gather_many(
            spec, [pl, pl * 2], table=tb, policy="ref",
            shard=shard))(pool, table)
        np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(outs[1]),
                                      np.asarray(want) * 2)
        # no pool-sized all-gather in the compiled sharded gather
        hlo = jax.jit(lambda pl, tb: vx.gather(
            spec, pl, table=tb, policy="ref",
            shard=shard)).lower(pool, table).compile().as_text()
        pool_elems = P * ps * K * D2
        for line in hlo.splitlines():
            if "all-gather" in line and f"{pool_elems}" in line:
                raise AssertionError(f"pool-sized all-gather:\n{line}")

    # the serving path: paged decode with the pool sharded through
    # ShardCtx.vx_pool_shard — bit-exact vs the replicated step
    mesh = make_test_mesh((8,), ("s",))
    ctx = ShardCtx(mesh=mesh, data_axes=(), model_axis=None,
                   seq_axes=("s",))
    pool_shard = ctx.vx_pool_shard(-4)
    assert pool_shard is not None and pool_shard.axes == ("s",)
    cfg = ModelConfig(name="paged-shard", d_model=32, n_layers=2,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=97,
                      head_dim=16, mlp="swiglu", scan_layers=True,
                      kernel_impl="ref", remat="none")
    params = init_params(cfg, jax.random.key(0))
    # num_pages = 2 slots x 8 pages: divides the 8 shards
    rep = dec.init_paged_cache(cfg, 2, 32, 4, jnp.float32)
    shd = rep
    tok = jnp.asarray([3, 9], jnp.int32)
    jrep = jax.jit(lambda p, c, t: dec.paged_decode_step(p, c, t, cfg,
                                                         None))
    jshd = jax.jit(lambda p, c, t: dec.paged_decode_step(
        p, c, t, cfg, None, pool_shard=pool_shard))
    for _ in range(5):
        lr, rep = jrep(params, rep, tok)
        ls, shd = jshd(params, shd, tok)
        np.testing.assert_array_equal(np.asarray(lr), np.asarray(ls))
        tok = jnp.argmax(lr.astype(jnp.float32), -1).astype(jnp.int32)

    # the pool itself placed sharded on its page axis: the prefill chunk
    # and the decode step write it in place, so it stays sharded, no
    # collective gathers it, and the step stays bit-exact
    from jax.sharding import NamedSharding, PartitionSpec
    on_pages = NamedSharding(mesh, PartitionSpec(None, "s"))
    jchunk = jax.jit(lambda p, c, t: dec.paged_prefill_chunk(
        p, c, t, cfg, None, slot=1, count=3))
    rep = jchunk(params, rep, jnp.arange(4, dtype=jnp.int32))
    shd = dict(shd, blocks=jax.device_put(shd["blocks"], on_pages))
    for prog, args in ((jchunk, (jnp.arange(4, dtype=jnp.int32),)),
                       (jshd, (tok,))):
        hlo = prog.lower(params, shd, *args).compile().as_text()
        for line in hlo.splitlines():
            m = re.search(r"= \w+\[([\d,]+)\]\S* all-gather\(", line)
            if m and np.prod([int(d) for d in m.group(1).split(",")]) \
                    >= rep["blocks"]["pos0"].size:
                raise AssertionError(f"pool-sized all-gather:\n{line}")
    shd = jchunk(params, shd, jnp.arange(4, dtype=jnp.int32))
    lr, rep = jrep(params, rep, tok)
    ls, shd = jshd(params, shd, tok)
    np.testing.assert_array_equal(np.asarray(lr), np.asarray(ls))
    for k, leaf in shd["blocks"].items():
        assert leaf.sharding.is_equivalent_to(on_pages, leaf.ndim), k
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(rep["blocks"][k]))
    print("CHECK_OK")


def check_quantized_pool_shard():
    """Sharded QUANTIZED paged gather (PR 9): int8 pool + per-page
    scales sharded on the page axis, dequant fused shard-locally — the
    scale one-hot contraction runs against the rebased local table, so
    the sharded lowering must be bit-exact vs the replicated one across
    mesh layouts, for full / partial / unallocated tables."""
    from repro import vx
    from repro.launch.mesh import make_test_mesh

    rng = np.random.default_rng(0)
    ps, pages, P, K, D2 = 4, 6, 16, 2, 8
    pool = jnp.asarray(rng.integers(-127, 128, (2, P, ps, K, D2)),
                       jnp.int8)
    scales = jnp.asarray(rng.uniform(0.01, 2.0, (2, P, K)), jnp.float32)
    spec = vx.Paged(page_size=ps, pages=pages, trail=2)
    tables = np.full((3, pages), -1, np.int32)
    tables[0, :pages] = rng.permutation(P)[:pages]
    tables[1, :3] = [15, 0, 7]
    table = jnp.asarray(tables)
    want = vx.gather(spec, pool, table=table, scales=scales, policy="ref")
    for shape, axes in [((8,), ("s",)), ((2, 4), ("a", "b")),
                        ((4, 2), ("a", "b"))]:
        mesh = make_test_mesh(shape, axes)
        shard = vx.Shard(axes=axes, axis=-4, mesh=mesh)
        got = jax.jit(lambda pl, sc, tb: vx.gather(
            spec, pl, table=tb, scales=sc, policy="ref",
            shard=shard))(pool, scales, table)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    print("CHECK_OK")


CHECKS = {
    "moe_ep_equivalence": check_moe_ep_equivalence,
    "sharded_train_step": check_sharded_train_step,
    "pipeline_equivalence": check_pipeline_equivalence,
    "elastic_reshard": check_elastic_reshard,
    "seq_parallel_decode": check_seq_parallel_decode,
    "longctx_fused_decode": check_longctx_fused_decode,
    "longctx_launch_gate": check_longctx_launch_gate,
    "sharded_vx_property": check_sharded_vx_property,
    "paged_pool_shard": check_paged_pool_shard,
    "quantized_pool_shard": check_quantized_pool_shard,
}

if __name__ == "__main__":
    CHECKS[sys.argv[1]]()
