"""Whole-step access fusion (core/accessfuse.py) — equivalence and the
launch-count regression gate.

The gate is jaxpr-level (jax.make_jaxpr): the fused decode step must issue
at least 2x fewer pallas kernel launches AND 2x fewer mask operands than
the per-access path for a 4-layer step.  No timing — CI-stable.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import vx
from repro.core import accessfuse, scg, shiftnet
from repro.models import decode as dec
from repro.models.transformer import ModelConfig, init_params


def _cfg(layers=4, hd=64, scan=False, impl="pallas", mlp="none", d_ff=0):
    return ModelConfig(
        name="fuse-test", d_model=2 * hd, n_layers=layers, n_heads=2,
        n_kv_heads=2, d_ff=d_ff, vocab=97, head_dim=hd, mlp=mlp,
        scan_layers=scan, kernel_impl=impl, remat="none")


# ---------------------------------------------------------------------------
# Scheduler: grouping, one launch, one concatenated mask operand
# ---------------------------------------------------------------------------

def _fused_split(*xs):
    # platform_policy off: exercise the merged KERNEL lowering (the TPU
    # decision) in interpret mode so launches are countable
    pairs = accessfuse.fuse_deinterleave(list(xs), 2, impl="pallas",
                                         platform_policy=False)
    return [f for pair in pairs for f in pair]


def _per_access_split(*xs):
    return [f for x in xs
            for f in vx.transpose(vx.Segment(n=x.shape[-1], fields=2), x,
                                  policy="pallas")]


def test_scheduler_merges_same_shape_group_into_one_launch():
    # 64*512 = 32768 elements each: above MIN_FUSED_ELEMS, stays pallas
    arrays = [jnp.arange(64 * 512, dtype=jnp.float32).reshape(64, 512) + i
              for i in range(4)]
    lf, mf = accessfuse.jaxpr_access_counts(_fused_split, *arrays)
    lp, mp = accessfuse.jaxpr_access_counts(_per_access_split, *arrays)
    assert lf == 1 and lp == 4, (lf, lp)
    # the merged 256 rows are whole 128-row chunks: the transpose route,
    # which reads no mask; each 64-row access keeps its shift plan's mask
    assert mf == 0 and mp == 4, (mf, mp)
    got = jax.jit(_fused_split)(*arrays)
    want = [f for x in arrays
            for f in vx.transpose(vx.Segment(n=x.shape[-1], fields=2), x,
                                  policy="ref")]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_merged_shift_plan_group_uploads_one_mask():
    # bfloat16 keeps the shift plans: the merged launch carries the
    # group's one shared mask operand, the per-access path one each
    arrays = [jnp.arange(64 * 512, dtype=jnp.bfloat16).reshape(64, 512) + i
              for i in range(4)]
    assert accessfuse.jaxpr_access_counts(_fused_split, *arrays) == (1, 1)
    assert accessfuse.jaxpr_access_counts(_per_access_split, *arrays) == (4, 4)


def test_scheduler_inlines_tiny_groups():
    tiny = [jnp.arange(16, dtype=jnp.float32).reshape(2, 8)] * 2
    launches, _ = accessfuse.jaxpr_access_counts(
        lambda *xs: accessfuse.fuse_deinterleave(list(xs), 2,
                                                 impl="pallas")[0],
        *tiny)
    assert launches == 0        # below MIN_FUSED_ELEMS -> XLA path


def test_scheduler_interleave_and_heterogeneous_gather():
    parts = [[jnp.arange(32, dtype=jnp.float32) + 10 * a,
              jnp.arange(32, dtype=jnp.float32) + 100 * a]
             for a in range(3)]
    outs = accessfuse.fuse_interleave(parts, impl="ref")
    for a, out in enumerate(outs):
        want = vx.transpose(vx.Segment(n=64, fields=2), parts[a],
                            policy="ref")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    # same (shape, vl), different (stride, offset): single fused kernel
    wins = [jnp.arange(8 * 64, dtype=jnp.float32).reshape(8, 64) * (a + 1)
            for a in range(3)]
    specs = [(2, 0), (3, 1), (1, 5)]
    sched = accessfuse.StepScheduler(impl="pallas", platform_policy=False)
    hs = [sched.gather_strided(w, s, o, 16)
          for w, (s, o) in zip(wins, specs)]
    sched.flush()
    for h, w, (s, o) in zip(hs, wins, specs):
        want = vx.gather(vx.Strided(n=64, stride=s, offset=o, vl=16), w,
                         policy="ref")
        np.testing.assert_array_equal(np.asarray(h.value), np.asarray(want))


# ---------------------------------------------------------------------------
# Fused decode step: bit-exact with the per-access oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scan", (False, True))
@pytest.mark.parametrize("impl", ("ref", "pallas"))
def test_fused_decode_matches_per_access(scan, impl):
    cfg = _cfg(layers=4, hd=16, scan=scan, impl=impl, mlp="swiglu", d_ff=64)
    params = init_params(cfg, jax.random.key(0))
    cache = dec.init_cache(cfg, 2, 16, jnp.float32)
    tok = jnp.array([3, 5], jnp.int32)
    fused = jax.jit(lambda p, c, t: dec.decode_step(p, c, t, cfg, None,
                                                    fuse=True))
    per = jax.jit(lambda p, c, t: dec.decode_step(p, c, t, cfg, None,
                                                  fuse=False))
    cf, cp = cache, cache
    for _ in range(3):      # several steps: append slot walks the ring
        lf, cf = fused(params, cf, tok)
        lp, cp = per(params, cp, tok)
        np.testing.assert_array_equal(np.asarray(lf), np.asarray(lp))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), cf, cp)


def test_decode_launch_count_regression_gate():
    """CI gate: fused 4-layer decode step issues >= 2x fewer kernel
    launches and mask operands than the per-access compiled path."""
    cfg = _cfg(layers=4, hd=64, scan=False, impl="pallas")
    params = init_params(cfg, jax.random.key(0))
    cache = dec.init_cache(cfg, 2, 64, jnp.float32)
    tok = jnp.array([3, 5], jnp.int32)

    def fused(p, c, t):
        return dec.decode_step(p, c, t, cfg, None, fuse=True)

    def per_access(p, c, t):
        return dec.decode_step(p, c, t, cfg, None, fuse=False)

    # pin the TPU lowering decision so the merged group is countable as a
    # kernel launch even in interpret mode (the default platform policy
    # would inline it on the XLA path here, giving 0 launches)
    with accessfuse.pinned_kernel_lowering():
        lf, mf = accessfuse.jaxpr_access_counts(fused, params, cache, tok)
    lp, mp = accessfuse.jaxpr_access_counts(per_access, params, cache, tok)
    # the merged split is 1024 float32 rows: the transpose route, no mask
    assert lf == 1 and mf == 0, (lf, mf)
    assert lp >= 4 and mp >= 4, (lp, mp)
    assert 2 * lf <= lp, (lf, lp)
    assert 2 * mf <= mp, (mf, mp)


# ---------------------------------------------------------------------------
# Plan bank (lax.switch) vs dynamic oracle — see also
# tests/test_property_shiftnet.py for the stride sweep
# ---------------------------------------------------------------------------

def test_bank_dispatch_under_jit_has_no_dynamic_cost_on_banked_path():
    # the switch carries ONE dynamic-fallback branch; banked branches use
    # compiled plans (constant masks -> no shiftcnt arithmetic operands)
    n, offset, vl = 128, 32, 8
    win = jnp.broadcast_to(jnp.arange(n, dtype=jnp.float32), (4, n))
    out = jax.jit(lambda w, s: accessfuse.bank_gather_strided(
        w, s, offset, vl))(win, jnp.int32(3))
    want = np.arange(n, dtype=np.float32)[offset + 3 * np.arange(vl)]
    np.testing.assert_array_equal(np.asarray(out),
                                  np.broadcast_to(want, (4, vl)))


# ---------------------------------------------------------------------------
# MoE compaction through the bank's runtime-count path
# ---------------------------------------------------------------------------

def test_compact_indices_matches_dynamic_network():
    rng = np.random.default_rng(0)
    for n in (8, 64, 128):
        for _ in range(5):
            mask = jnp.asarray(rng.random(n) < 0.4)
            ids = jnp.arange(n, dtype=jnp.int32)
            shift, valid = scg.compaction_counts(mask)
            res = shiftnet.gather_network(ids, shift, valid)
            want = np.asarray(res.payload)
            got = np.asarray(accessfuse.compact_indices(mask, n))
            total = int(np.asarray(mask).sum())
            np.testing.assert_array_equal(got[:total], want[:total])


def test_moe_earth_dispatch_still_matches_argsort():
    from repro.models.moe import MoESpec, init_moe, moe_ffn_local
    d, E, k, T = 32, 4, 2, 64
    x = jax.random.normal(jax.random.key(1), (T, d))
    params = init_moe(jax.random.key(0), d,
                      MoESpec(n_experts=E, top_k=k, d_ff=64), jnp.float32)

    def run(dispatch):
        spec = MoESpec(n_experts=E, top_k=k, d_ff=64, dispatch=dispatch)
        return moe_ffn_local(params["router"], params["wg"], params["wu"],
                             params["wo"], x, spec, model_axis=None,
                             data_axes=(), n_shards=1)[0]

    np.testing.assert_allclose(np.asarray(run("earth")),
                               np.asarray(run("sort")), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# Input pipeline: pack+unpack elision (plan composition = identity)
# ---------------------------------------------------------------------------

def test_segment_roundtrip_plans_compose_to_identity():
    from repro.core import shiftplan
    for fields in (2, 4):
        n = 32 * fields
        ipl = shiftplan.interleave_plan(n, fields)
        dpl = shiftplan.deinterleave_plan(n, fields)
        x = np.arange(max(ipl.n, dpl.n))
        mid = shiftplan.apply_np(ipl, x[:ipl.n])[:n]
        back = shiftplan.apply_np(dpl, np.pad(mid, (0, dpl.n - n)))[:n]
        np.testing.assert_array_equal(back, x[:n])


def test_pipeline_fused_bit_exact_and_same_state():
    from repro.data.pipeline import DataConfig, SyntheticAoSPipeline
    cfg = DataConfig(vocab=101, seq_len=16, global_batch=4, seed=3)
    a = SyntheticAoSPipeline(cfg, process_index=1, process_count=2)
    b = SyntheticAoSPipeline(cfg, process_index=1, process_count=2)
    for _ in range(3):
        ba = a.next_batch(fused=True)
        bb = b.next_batch(fused=False)
        assert set(ba) == set(bb)
        for key in ba:
            np.testing.assert_array_equal(np.asarray(ba[key]),
                                          np.asarray(bb[key]))
    assert a.state_dict() == b.state_dict()


def test_pack_unpack_fused_matches_roundtrip():
    from repro.data import aos
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, 50, (2, 8), dtype=np.int32))
    labels = jnp.asarray(rng.integers(0, 50, (2, 8), dtype=np.int32))
    weights = jnp.asarray(rng.random((2, 8), dtype=np.float32))
    docs = jnp.asarray(rng.integers(0, 9, (2, 8), dtype=np.int32))
    want = aos.unpack_records(aos.pack_records(toks, labels, weights, docs))
    got = aos.pack_unpack_fused(toks, labels, weights, docs)
    for key in want:
        np.testing.assert_array_equal(np.asarray(got[key]),
                                      np.asarray(want[key]))


# ---------------------------------------------------------------------------
# Whole-step LSDO (multi-access super-transaction)
# ---------------------------------------------------------------------------

def test_load_strided_many_matches_per_access():
    from repro.core import lsdo
    buf = jnp.arange(4096, dtype=jnp.float32) * 5 + 3
    plans = [lsdo.plan_strided(0, 2, 64, 128),
             lsdo.plan_strided(7, 3, 40, 128),
             lsdo.plan_strided(513, 4, 32, 128),
             lsdo.plan_strided(1, -4, 50, 128),
             lsdo.plan_strided(9, 0, 0, 128)]      # vl=0 edge
    outs = lsdo.load_strided_many(buf, plans)
    for p, o in zip(plans, outs):
        want = lsdo.load_strided(buf, p, batched=False) if p.vl > 0 \
            else np.zeros((0,), np.float32)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(want))
