"""Quantized paged KV pool (PR 9): int8/fp8 pages + per-page scales.

Lowering level — dequant rides the SAME single page-gather program
(zero extra gather equations, one pinned kernel launch for the fused
step), scatter quantizes on write with a monotone scale widen that is
duplicate-physical-page safe; decode level — fused == unfused
bit-exact, logits track the float32 oracle within the quantization
bound over a page_size x slots sweep; serve level — scales travel with
physical pages through prefix adoption and CoW fork (bit-exact vs the
non-shared quantized oracle), the invariant audit covers scale
liveness, memory accounting counts the scale side tensor, and the
chaos / fleet gates hold at int8."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import vx
from repro.configs import get_arch
from repro.core import accessfuse, quant
from repro.models import decode as dec
from repro.models.transformer import ModelConfig, init_params
from repro.serve.paged_cache import PagedCache
from repro.serve.scheduler import Scheduler


def _cfg(layers=2, hd=16, scan=False, impl="ref", positions=2,
         mlp="none", d_ff=0):
    return ModelConfig(
        name="quant-test", d_model=2 * hd, n_layers=layers, n_heads=2,
        n_kv_heads=2, d_ff=d_ff, vocab=97, head_dim=hd, mlp=mlp,
        block_pattern=("attn",) * positions,
        window_pattern=(None,) * positions,
        moe_pattern=(False,) * positions,
        scan_layers=scan, kernel_impl=impl, remat="none")


def _count_gathers(fn, *args) -> int:
    def rec(jaxpr):
        c = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "gather":
                c += 1
            for v in eqn.params.values():
                for sub in accessfuse._child_jaxprs(v):
                    c += rec(sub)
        return c
    return rec(jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr)


@functools.lru_cache(maxsize=None)
def _arch_cfg_params(arch="qwen3-0.6b"):
    cfg = get_arch(arch).smoke
    return cfg, init_params(cfg, jax.random.key(0))


# ---------------------------------------------------------------------------
# vx lowering: quantized gather / scatter
# ---------------------------------------------------------------------------

def test_quantized_gather_matches_manual_dequant():
    """out = pool[table-indexed].astype(f32) * per-page-per-head scale,
    zeros through unallocated (-1) entries — fp8 garbage in untouched
    pages must never leak through the mask."""
    rng = np.random.default_rng(0)
    ps, pages, P, K, D = 4, 3, 8, 2, 6
    pool = jnp.asarray(rng.integers(-127, 128, (P, ps, K, D)), jnp.int8)
    scales = jnp.asarray(rng.uniform(0.01, 2.0, (P, K)), jnp.float32)
    spec = vx.Paged(page_size=ps, pages=pages, trail=2)
    table = jnp.asarray([[2, 0, -1], [5, -1, -1]], np.int32)
    out = vx.gather(spec, pool, table=table, scales=scales)
    assert out.shape == (2, pages * ps, K, D)
    assert out.dtype == jnp.float32
    pn = np.asarray(pool, np.float32) * np.asarray(scales)[:, None, :, None]
    want = np.zeros((2, pages * ps, K, D), np.float32)
    want[0, :4], want[0, 4:8] = pn[2], pn[0]
    want[1, :4] = pn[5]
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)


def test_quantized_gather_adds_zero_gather_eqns():
    """The scale lookup is a one-hot contraction, NOT a second gather:
    the quantized program must cost exactly as many gather equations as
    the float one — the fused-dequant acceptance gate at the jaxpr
    level."""
    ps, pages, P, K, D = 4, 3, 8, 2, 6
    poolf = jnp.zeros((P, ps, K, D), jnp.float32)
    poolq = jnp.zeros((P, ps, K, D), jnp.int8)
    scales = jnp.ones((P, K), jnp.float32)
    spec = vx.Paged(page_size=ps, pages=pages, trail=2)
    table = jnp.asarray([[2, 0, -1]], np.int32)
    gf = _count_gathers(lambda p, t: vx.gather(spec, p, table=t),
                        poolf, table)
    gq = _count_gathers(
        lambda p, s, t: vx.gather(spec, p, table=t, scales=s),
        poolq, scales, table)
    assert gq == gf, (gq, gf)


def test_quantized_scatter_roundtrips_within_bound():
    ps, pages, P, K, D = 4, 2, 6, 2, 3
    pool = jnp.zeros((P, ps, K, D), jnp.int8)
    scales = jnp.zeros((P, K), jnp.float32)
    spec = vx.Paged(page_size=ps, pages=pages, trail=2)
    table = jnp.asarray([[1, -1], [3, 0], [-1, -1]], np.int32)
    vals = jnp.asarray(np.random.default_rng(1).normal(size=(3, K, D)),
                       jnp.float32)
    pos = jnp.asarray([2, 5, -1], np.int32)
    npool, nscl = vx.scatter(spec, pool, vals, table=table, pos=pos,
                             scales=scales)
    assert npool.dtype == jnp.int8 and nscl.shape == (P, K)
    got = np.asarray(npool, np.float32) * np.asarray(nscl)[:, None, :, None]
    vn = np.asarray(vals)
    for row, (pg, off) in ((0, (1, 2)), (1, (0, 1))):
        bound = quant.error_bound("int8", float(np.abs(vn[row]).max()))
        assert np.abs(got[pg, off] - vn[row]).max() <= bound * 1.001
    # dropped rows / unallocated pages leave pool AND scales untouched
    assert float(np.abs(got[1, 3]).max()) == 0.0
    untouched = np.delete(np.asarray(nscl), [0, 1, 3], axis=0)
    np.testing.assert_array_equal(untouched, 0.0)


def test_quantized_scatter_duplicate_physical_page_is_safe():
    """Two batch rows landing in the SAME physical page the same step
    (adopted prefixes make this real): the scale must widen to cover
    both beats and BOTH land within bound — a read-modify-write race
    here would corrupt one of them."""
    ps, pages, P, K, D = 4, 2, 4, 2, 3
    pool = jnp.zeros((P, ps, K, D), jnp.int8)
    scales = jnp.zeros((P, K), jnp.float32)
    spec = vx.Paged(page_size=ps, pages=pages, trail=2)
    table = jnp.asarray([[2, -1], [2, -1]], np.int32)   # same phys page
    vals = jnp.asarray([[[0.1] * D] * K, [[50.0] * D] * K], jnp.float32)
    pos = jnp.asarray([0, 1], np.int32)                 # offsets 0, 1
    npool, nscl = vx.scatter(spec, pool, vals, table=table, pos=pos,
                             scales=scales)
    got = np.asarray(npool, np.float32) * np.asarray(nscl)[:, None, :, None]
    np.testing.assert_allclose(got[2, 1], 50.0, rtol=1e-2)
    bound = quant.error_bound("int8", 50.0)             # widened scale
    assert np.abs(got[2, 0] - 0.1).max() <= bound * 1.001


def test_quantized_scatter_scale_widens_monotonically():
    """Append small then large into one page: the scale only WIDENS
    (never shrinks — shared CoW pages are immutable, so a shrink would
    need a rewrite), residents are rescaled and stay within ~one extra
    half-step of error per widen event."""
    ps, pages, P, K, D = 4, 1, 2, 1, 2
    pool = jnp.zeros((P, ps, K, D), jnp.int8)
    scales = jnp.zeros((P, K), jnp.float32)
    spec = vx.Paged(page_size=ps, pages=pages, trail=2)
    table = jnp.asarray([[0]], np.int32)
    s_hist = []
    for step, mag in enumerate([0.5, 8.0, 2.0]):
        vals = jnp.full((1, K, D), mag, jnp.float32)
        pool, scales = vx.scatter(spec, pool, vals, table=table,
                                  pos=jnp.asarray([step], np.int32),
                                  scales=scales)
        s_hist.append(float(scales[0, 0]))
    assert s_hist == sorted(s_hist)                     # monotone
    assert s_hist[-1] == pytest.approx(8.0 / 127.0)     # never shrank
    got = np.asarray(pool, np.float32)[0, :, 0, 0] * s_hist[-1]
    # resident 0.5 was rescaled through one widen: <= 2 half-steps
    assert abs(got[0] - 0.5) <= 2 * quant.error_bound("int8", 8.0)
    assert abs(got[1] - 8.0) <= quant.error_bound("int8", 8.0) * 1.001
    assert abs(got[2] - 2.0) <= quant.error_bound("int8", 8.0) * 1.001


# ---------------------------------------------------------------------------
# decode: fused step semantics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ps,slots", [(4, 1), (4, 3), (8, 3), (16, 1)])
def test_quantized_decode_tracks_float_oracle(ps, slots):
    """Forced-teacher sweep: step the quantized and float32 pools on the
    SAME token stream (the float stream's argmax) and require the
    quantized logits to stay within the quantization error bound of the
    float oracle at every step — across page sizes (many small pages =
    many widen events) and batch widths."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.key(0))
    cf = dec.init_paged_cache(cfg, slots, 32, ps, jnp.float32)
    cq = dec.init_paged_cache(cfg, slots, 32, ps, jnp.float32,
                              quantize="int8")
    stepf = jax.jit(lambda p, c, t: dec.paged_decode_step(
        p, c, t, cfg, None, fuse=True))
    tok = jnp.asarray(np.arange(3, 3 + slots), jnp.int32)
    worst = 0.0
    for _ in range(9):
        lf, cf = stepf(params, cf, tok)
        lq, cq = stepf(params, cq, tok)
        worst = max(worst, float(jnp.max(jnp.abs(lf - lq))))
        tok = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    scale = float(jnp.max(jnp.abs(lf)))
    assert worst <= max(0.08, 0.03 * scale), (worst, scale)
    assert not dec.paged_invariants(cfg, cq)


def test_quantized_fused_equals_unfused_bit_exact():
    """fuse=True vs fuse=False must agree BIT-EXACTLY on the quantized
    pool — both arms read pre-append pages plus the fresh float beat, so
    any divergence is a lowering bug, not quantization noise."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.key(0))
    cm = {f: dec.init_paged_cache(cfg, 2, 32, 8, jnp.float32,
                                  quantize="int8") for f in (True, False)}
    tok = jnp.asarray([3, 5], jnp.int32)
    for step in range(6):
        outs = {}
        for f in (True, False):
            outs[f], cm[f] = dec.paged_decode_step(
                params, cm[f], tok, cfg, None, fuse=f)
        np.testing.assert_array_equal(np.asarray(outs[True]),
                                      np.asarray(outs[False]))
        tok = jnp.argmax(outs[True], axis=-1).astype(jnp.int32)
    # the two pools took identical int-level writes
    for k, leaf in cm[True]["blocks"].items():
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(cm[False]["blocks"][k]))


def test_quantized_fused_step_one_gather_one_launch():
    """The quantized acceptance gate mirrors the float one: fusing the
    step saves the same (leaves x superblocks - 1) page gathers, and the
    pinned-kernel fused step still issues ONE launch — dequant rides the
    existing program instead of adding a pass; the dequantized float32
    split takes the transpose route, which reads no mask."""
    cfg_ref = _cfg(layers=4, hd=64)
    params = init_params(cfg_ref, jax.random.key(0))
    cache = dec.init_paged_cache(cfg_ref, 2, 64, 16, jnp.float32,
                                 quantize="int8")
    tok = jnp.asarray([3, 5], jnp.int32)
    gf = _count_gathers(
        lambda p, c, t: dec.paged_decode_step(p, c, t, cfg_ref, None,
                                              fuse=True),
        params, cache, tok)
    gp = _count_gathers(
        lambda p, c, t: dec.paged_decode_step(p, c, t, cfg_ref, None,
                                              fuse=False),
        params, cache, tok)
    # the quantized append takes four lookups a scatter (page ids, the
    # scale row twice, the resident page), once per leaf in place instead
    # of once per leaf per superblock
    assert gp - gf == (2 * 2 - 1) + 4 * (2 * 2 - 2), (gf, gp)

    cfg = _cfg(layers=4, hd=64, impl="pallas")
    cache = dec.init_paged_cache(cfg, 2, 64, 16, jnp.float32,
                                 quantize="int8")

    def fused(p, c, t):
        return dec.paged_decode_step(p, c, t, cfg, None, fuse=True)

    with accessfuse.pinned_kernel_lowering():
        lf, mf = accessfuse.jaxpr_access_counts(fused, params, cache, tok)
    assert lf == 1 and mf == 0, (lf, mf)


def test_quantized_plan_cache_steady_state_under_jit():
    """scale_dtype keys the plan (a distinct entry from the float
    program), and steady-state quantized stepping must not re-miss."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.key(0))
    cache = dec.init_paged_cache(cfg, 2, 16, 4, jnp.float32,
                                 quantize="int8")
    tok = jnp.asarray([3, 5], jnp.int32)
    jp = jax.jit(lambda p, c, t: dec.paged_decode_step(p, c, t, cfg, None))
    _, cache = jp(params, cache, tok)
    warm = vx.PLANS.stats()["misses"]
    for _ in range(4):
        _, cache = jp(params, cache, tok)
    assert vx.PLANS.stats()["misses"] == warm


def test_invariants_cover_scale_liveness():
    """The audit extends to the scale side tensor: a NaN or negative
    scale (a poisoned page every gather would spread) and a missing scl
    leaf must both trip."""
    cfg = _cfg()
    cache = dec.init_paged_cache(cfg, 2, 16, 4, jnp.float32,
                                 quantize="int8")
    assert not dec.paged_invariants(cfg, cache)
    bad = dict(cache, blocks=dict(
        cache["blocks"], scl0=cache["blocks"]["scl0"].at[0, 0, 0].set(
            jnp.nan)))
    assert any("scl" in v or "scale" in v
               for v in dec.paged_invariants(cfg, bad))
    neg = dict(cache, blocks=dict(
        cache["blocks"], scl0=cache["blocks"]["scl0"].at[0, 1, 0].set(
            -1.0)))
    assert dec.paged_invariants(cfg, neg)
    missing = dict(cache, blocks={k: v for k, v in
                                  cache["blocks"].items() if k != "scl1"})
    assert dec.paged_invariants(cfg, missing)


# ---------------------------------------------------------------------------
# serve: accounting, prefix interop, chaos
# ---------------------------------------------------------------------------

def test_page_bytes_counts_scale_side_tensor():
    """Satellite accounting fix: page_bytes is dtype-aware AND includes
    the per-page scale rows; used_cache_bytes scales those with pages in
    use instead of charging the whole side tensor as recurrent state."""
    cfg, _ = _arch_cfg_params()
    pcf = PagedCache(cfg, 2, 32, 8)
    pcq = PagedCache(cfg, 2, 32, 8, kv_quant="int8")
    scl_pp = sum((leaf.size // leaf.shape[1]) * leaf.dtype.itemsize
                 for k, leaf in pcq.state["blocks"].items()
                 if k.startswith("scl"))
    pool_pp = sum((leaf.size // leaf.shape[1]) * leaf.dtype.itemsize
                  for leaf in pcq.state["blocks"].values()
                  if hasattr(leaf, "ndim") and leaf.ndim == 5)
    assert scl_pp > 0
    assert pcq.page_bytes() == pool_pp + scl_pp
    # int8 pool page = 1/4 the float page; scale overhead keeps the
    # ratio just under 4x, still well past the ~3.5x acceptance floor
    ratio = pcf.page_bytes() / pcq.page_bytes()
    assert 3.5 <= ratio <= 4.0, ratio
    # used bytes scale with pages in use, not allocation
    base_q, base_f = pcq.used_cache_bytes(), pcf.used_cache_bytes()
    assert pcq.total_cache_bytes() < pcf.total_cache_bytes()
    assert base_q <= base_f


def test_quantized_prefix_sharing_bit_exact_vs_nonshared():
    """Adopted pages carry their scales (same physical page, same scale
    row, same ints): a prefix-sharing quantized scheduler must be
    BIT-EXACT vs a non-sharing quantized one — including a partial-tail
    CoW fork, which copies the source page's scale into the fork."""
    cfg, params = _arch_cfg_params()
    shared_full = [3, 5, 7, 9, 2, 4, 6, 8] + [11, 13]
    forked_tail = [3, 5, 7, 9, 2, 4, 9, 9, 12]   # diverges mid-page-2
    for pa, pb in ((shared_full, shared_full[:-2] + [12, 10]),
                   (shared_full, forked_tail)):
        outs = {}
        for name, pc in (("shared", True), ("oracle", False)):
            s = Scheduler(cfg, params, slots=2, max_len=32, page_size=4,
                          num_pages=16, kv_quant="int8", prefix_cache=pc,
                          debug_invariants=True)
            a, b = s.add_request(list(pa)), s.add_request(list(pb))
            outs[name] = [(step[a], step[b]) for step in
                          (s.step() for _ in range(6))]
            s.cache.check_invariants()
        assert outs["shared"] == outs["oracle"]


def test_fork_copies_scale_and_isolates_source():
    """dec-level CoW audit: the fork's page gets the SOURCE's scale row
    (its resident ints only decode correctly under it), and appending
    into the fork afterwards widens the FORK's scale only — the shared
    source page and scale stay byte-identical."""
    cfg = _cfg()
    cache = dec.init_paged_cache(cfg, 2, 16, 4, jnp.float32,
                                 quantize="int8")
    params = init_params(cfg, jax.random.key(0))
    tok = jnp.asarray([3, 5], jnp.int32)
    for _ in range(3):                       # slot pages get real beats
        _, cache = dec.paged_decode_step(params, cache, tok, cfg, None)
    src = int(cache["table"][0, 0])
    cache = dec.paged_fork_page(cfg, cache, jnp.int32(1), jnp.int32(0),
                                jnp.int32(src), pos_to=jnp.int32(2))
    dst = int(cache["table"][1, 0])
    assert dst != src
    blocks = cache["blocks"]
    for i in range(2):
        np.testing.assert_array_equal(
            np.asarray(blocks[f"scl{i}"][:, dst]),
            np.asarray(blocks[f"scl{i}"][:, src]))
        np.testing.assert_array_equal(
            np.asarray(blocks[f"pos{i}"][:, dst, :2]),
            np.asarray(blocks[f"pos{i}"][:, src, :2]))
    before = {i: (np.asarray(blocks[f"pos{i}"][:, src]).copy(),
                  np.asarray(blocks[f"scl{i}"][:, src]).copy())
              for i in range(2)}
    # ONLY the borrower steps (slot 0 masked inactive — it still owns
    # src and would legitimately append there): the write lands in the
    # fork, and the shared source page + scale stay byte-identical
    _, cache = dec.paged_decode_step(params, cache,
                                     jnp.asarray([7, 7], jnp.int32),
                                     cfg, None,
                                     active=jnp.asarray([False, True]))
    blocks = cache["blocks"]
    for i in range(2):
        np.testing.assert_array_equal(np.asarray(blocks[f"pos{i}"][:, src]),
                                      before[i][0])
        np.testing.assert_array_equal(np.asarray(blocks[f"scl{i}"][:, src]),
                                      before[i][1])


@pytest.mark.parametrize("seed", (0, 1))
def test_chaos_preempt_replay_holds_at_int8(seed):
    """The PR 6 chaos gate re-run on the quantized pool: preemption
    replays a request's tokens through quantize-on-write from scratch —
    every request terminates typed and the (scale-extended) invariant
    audit holds every tick."""
    from repro.ft.straggler import StepWatchdog
    from repro.serve.chaos import ChaosConfig, FaultPlan, run_plan
    from repro.serve.lifecycle import TERMINAL_STATES

    class _StepClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            self.t += 0.01
            return self.t

    cfg, params = _arch_cfg_params()
    sched = Scheduler(cfg, params, slots=2, max_len=16, page_size=4,
                      num_pages=6, kv_quant="int8", guard_nan=True,
                      queue_depth=3, watchdog=StepWatchdog(),
                      clock=_StepClock())
    plan = FaultPlan(ChaosConfig(seed=seed, requests=6, steps=32,
                                 max_ticks=256))
    report = run_plan(sched, plan)
    assert report.ticks < plan.cfg.max_ticks
    assert sched.drained()
    assert report.all_terminal, report.states
    for r in report.submitted:
        assert r.state in TERMINAL_STATES
    assert report.invariant_checks >= report.ticks


def test_fleet_migration_holds_at_int8():
    """The PR 7 fleet gate at int8: replica death migrates requests by
    replay into a fresh quantized pool; the fleet audit (which runs the
    per-replica scale-extended invariants) holds every tick."""
    from repro.serve.chaos import (FleetChaosConfig, FleetFaultPlan,
                                   StepClock, run_fleet_plan)
    from repro.serve.fleet import FleetRouter
    from repro.serve.lifecycle import TERMINAL_STATES

    cfg, params = _arch_cfg_params()
    fl = FleetRouter(cfg, params, replicas=2, slots=2, max_len=16,
                     page_size=4, num_pages=6, kv_quant="int8",
                     queue_depth=3, guard_nan=True, clock=StepClock(),
                     watchdog_hard_limit=30.0, hard_breach_limit=1,
                     heartbeat_ticks=4)
    plan = FleetFaultPlan(FleetChaosConfig(seed=1, requests=6, steps=24,
                                           max_ticks=512))
    report = run_fleet_plan(fl, plan)
    assert report.ticks < plan.cfg.max_ticks
    assert fl.drained()
    assert report.all_terminal, report.states
    for r in report.submitted:
        assert r.state in TERMINAL_STATES
    assert report.audits == report.ticks
