"""Paged KV runtime: vx.Paged lowering, paged decode vs the dense-cache
oracle, page reclamation, and the fused-paged-gather jaxpr gates.

Bit-exactness contract: for windowless attention layers the paged decode
step must reproduce the dense decode step's logits BIT-EXACTLY (the page
gather reconstructs the same (B, S, K, 2D) array the dense cache holds;
everything downstream is the identical computation).  Sliding-window
layers trade the ring buffer for an attention-time mask — same attended
set, different storage order — and are checked with allclose.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import vx
from repro.configs import get_arch
from repro.core import accessfuse, scg
from repro.models import decode as dec
from repro.models.transformer import ModelConfig, init_params


def _cfg(layers=2, hd=16, scan=False, impl="ref", positions=1, window=None,
         mlp="swiglu", d_ff=64):
    return ModelConfig(
        name="paged-test", d_model=2 * hd, n_layers=layers, n_heads=2,
        n_kv_heads=2, d_ff=d_ff, vocab=97, head_dim=hd, mlp=mlp,
        block_pattern=("attn",) * positions,
        window_pattern=(window,) * positions,
        moe_pattern=(False,) * positions,
        scan_layers=scan, kernel_impl=impl, remat="none")


def _count_gathers(fn, *args) -> int:
    """`gather` equations anywhere in the jaxpr (page-table takes; also
    counts embed/table lookups — callers compare paths, not absolutes)."""
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


# ---------------------------------------------------------------------------
# vx.Paged lowering
# ---------------------------------------------------------------------------

def test_paged_gather_matches_manual_take():
    rng = np.random.default_rng(0)
    ps, pages, P = 4, 3, 8
    pool = jnp.asarray(rng.normal(size=(2, P, ps, 2, 6)), jnp.float32)
    spec = vx.Paged(page_size=ps, pages=pages, trail=2)
    table = jnp.asarray([[2, 0, -1], [5, -1, -1]], np.int32)
    out = vx.gather(spec, pool, table=table)
    assert out.shape == (2, 2, pages * ps, 2, 6)
    pn = np.asarray(pool)
    want = np.zeros((2, 2, pages * ps, 2, 6), np.float32)
    want[:, 0, :4], want[:, 0, 4:8] = pn[:, 2], pn[:, 0]
    want[:, 1, :4] = pn[:, 5]
    np.testing.assert_array_equal(np.asarray(out), want)


def test_paged_scatter_appends_and_drops():
    ps, pages, P = 4, 2, 4
    pool = jnp.zeros((P, ps, 3), jnp.float32)
    spec = vx.Paged(page_size=ps, pages=pages, trail=1)
    table = jnp.asarray([[1, -1], [3, 0], [-1, -1]], np.int32)
    vals = jnp.asarray([[1., 1, 1], [2, 2, 2], [3, 3, 3]])
    # row0 pos 2 -> page 1 off 2; row1 pos 5 -> logical page 1 = phys 0,
    # off 1; row2 dropped (pos < 0); unallocated pages drop too
    pos = jnp.asarray([2, 5, -1], np.int32)
    out = np.asarray(vx.scatter(spec, pool, vals, table=table, pos=pos))
    want = np.zeros((P, ps, 3), np.float32)
    want[1, 2] = 1.0
    want[0, 1] = 2.0
    np.testing.assert_array_equal(out, want)
    # writes through an UNALLOCATED entry or past the logical capacity
    # are dropped (never clamped into a wrong page)
    out2 = np.asarray(vx.scatter(spec, pool, vals, table=table,
                                 pos=jnp.asarray([6, -1, 99], np.int32)))
    np.testing.assert_array_equal(out2, np.zeros_like(out2))


@pytest.mark.parametrize("quantize", (None, "int8"))
def test_paged_stack_access_matches_each_layer_alone(quantize):
    """A pool stacked over layers, addressed in place: one scatter of
    per-layer beats (``(L, *batch, *trail)`` values) writes what a scatter
    into each layer's own pool writes, and ``lead=l`` writes or reads
    layer ``l`` of the stack exactly as on that layer alone — float and
    quantized (scales widened per layer) alike, drops included."""
    rng = np.random.default_rng(3)
    L, ps, pages, P, K, D = 3, 4, 2, 5, 2, 3
    spec = vx.Paged(page_size=ps, pages=pages, trail=2)
    dtype = jnp.int8 if quantize else jnp.float32
    pool = jnp.asarray(rng.integers(-9, 9, (L, P, ps, K, D)), dtype)
    scales = (jnp.asarray(rng.uniform(0.1, 1.0, (L, P, K)), jnp.float32)
              if quantize else None)
    table = jnp.asarray([[1, -1], [3, 0], [-1, -1]], np.int32)
    pos = jnp.asarray([2, 5, 1], np.int32)
    vals = jnp.asarray(rng.normal(size=(L, 3, K, D)), jnp.float32)

    def pair(out):                  # (pool,) or (pool, scales)
        return out if quantize else (out,)

    def alone(layer, v):            # the layer's own pool, scattered
        return pair(vx.scatter(
            spec, pool[layer], v, table=table, pos=pos,
            scales=None if scales is None else scales[layer]))

    def check(got, want):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    every = pair(vx.scatter(spec, pool, vals, table=table, pos=pos,
                            scales=scales))
    check(every, [jnp.stack(x) for x in
                  zip(*(alone(layer, vals[layer]) for layer in range(L)))])
    one = pair(vx.scatter(spec, pool, vals[1], table=table, pos=pos,
                          scales=scales, lead=jnp.asarray(1)))
    new1 = alone(1, vals[1])
    check(one, [x.at[1].set(n) for x, n in
                zip((pool,) if scales is None else (pool, scales), new1)])
    read = vx.gather(spec, one[0], table=table[:2], lead=jnp.asarray(1),
                     scales=None if scales is None else one[1])
    np.testing.assert_array_equal(np.asarray(read), np.asarray(vx.gather(
        spec, one[0][1], table=table[:2],
        scales=None if scales is None else one[1][1])))


def test_append_paged_token_interleaves_beat():
    from repro.kernels import kv_interleaved
    ps, pages, P, H, d = 4, 2, 4, 2, 3
    pool = jnp.zeros((P, ps, H, 2 * d), jnp.float32)
    table = jnp.asarray([[2, -1]], np.int32)
    k = jnp.arange(H * d, dtype=jnp.float32).reshape(1, H, d)
    v = k + 100
    out = np.asarray(kv_interleaved.append_paged_token(
        pool, k, v, table, jnp.asarray([1], np.int32)))
    beat = np.asarray(kv_interleaved.interleave_kv(k, v))[0]
    want = np.zeros_like(out)
    want[2, 1] = beat
    np.testing.assert_array_equal(out, want)


def test_paged_program_cached_by_geometry_not_table():
    """One compiled program per page GEOMETRY, reused across requests
    (different runtime tables); a different page size is a new entry."""
    pool = jnp.zeros((4, 4, 2), jnp.float32)
    spec = vx.Paged(page_size=4, pages=2, trail=1)
    t1 = jnp.asarray([[0, 1]], np.int32)
    t2 = jnp.asarray([[3, -1]], np.int32)
    vx.PLANS.clear()
    vx.gather(spec, pool, table=t1, policy="ref")
    m1 = vx.PLANS.stats()["misses"]
    vx.gather(spec, pool, table=t2, policy="ref")   # table is runtime
    assert vx.PLANS.stats()["misses"] == m1
    pool2 = jnp.zeros((8, 2, 2), jnp.float32)
    vx.gather(vx.Paged(page_size=2, pages=2, trail=1), pool2, table=t1,
              policy="ref")
    assert vx.PLANS.stats()["misses"] > m1
    # dtype participates too (the PR 3 collision rule)
    vx.gather(spec, pool.astype(jnp.bfloat16), table=t1, policy="ref")
    assert vx.PLANS.stats()["misses"] > m1 + 1


def test_paged_gather_many_is_one_program():
    """The whole-step fused paged read is ONE gather over the stacked
    pools; the per-leaf path pays one per pool."""
    rng = np.random.default_rng(1)
    ps, pages, P = 4, 4, 8
    pools = [jnp.asarray(rng.normal(size=(2, P, ps, 2, 6)), jnp.float32)
             for _ in range(3)]
    table = jnp.asarray([[0, 3, -1, -1], [7, 2, 5, 1]], np.int32)
    spec = vx.Paged(page_size=ps, pages=pages, trail=2)

    fused = lambda a, b, c, t: vx.gather_many(spec, [a, b, c], table=t)
    per = lambda a, b, c, t: [vx.gather(spec, p, table=t)
                              for p in (a, b, c)]
    assert _count_gathers(fused, *pools, table) == 1
    assert _count_gathers(per, *pools, table) == 3
    got = fused(*pools, table)
    want = per(*pools, table)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_paged_shard_axis_validation():
    pool = jnp.zeros((4, 4, 2), jnp.float32)
    spec = vx.Paged(page_size=4, pages=2, trail=1)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",))
    bad = vx.Shard(axes=("x",), axis=-1, mesh=mesh)
    with pytest.raises(ValueError, match="page-pool axis"):
        vx.gather(spec, pool, table=jnp.zeros((1, 2), jnp.int32),
                  shard=bad)


def test_indexed_static_routing_promotes_to_plan():
    """Host-known (shift, valid) fold into the spec, compile through the
    plan stage, and match the dynamic network bit-exactly."""
    n = 32
    shift, valid = scg.gather_counts(n, 3, 2, 7)
    buf = jnp.arange(n, dtype=jnp.float32) * 2 + 1
    dyn = vx.gather(vx.Indexed(n=n), buf, shift=jnp.asarray(shift),
                    valid=jnp.asarray(valid))
    vx.PLANS.clear()
    static = vx.gather(vx.Indexed(n=n), buf, shift=np.asarray(shift),
                       valid=np.asarray(valid))
    np.testing.assert_array_equal(np.asarray(static), np.asarray(dyn))
    m = vx.PLANS.stats()["misses"]
    assert m >= 1
    vx.gather(vx.Indexed(n=n), buf, shift=np.asarray(shift),
              valid=np.asarray(valid))          # same routing: cache hit
    assert vx.PLANS.stats()["misses"] == m
    # spec-folded form is equivalent to operand promotion
    spec = vx.Indexed(n=n, routing=(tuple(np.asarray(shift).tolist()),
                                    tuple(np.asarray(valid).tolist())))
    np.testing.assert_array_equal(
        np.asarray(vx.gather(spec, buf)), np.asarray(dyn))


# ---------------------------------------------------------------------------
# Paged decode vs the dense-cache oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page_size", (4, 8, 16))
@pytest.mark.parametrize("slots", (1, 3))
def test_paged_decode_bit_exact_vs_dense_sweep(page_size, slots):
    """Property sweep over (page_size, slots): fused AND per-access paged
    decode reproduce the dense decode step bit-exactly, step by step."""
    cfg = _cfg(layers=2, hd=16, scan=True)
    params = init_params(cfg, jax.random.key(0))
    max_len = 16
    dense = dec.init_cache(cfg, slots, max_len, jnp.float32)
    paged = dec.init_paged_cache(cfg, slots, max_len, page_size,
                                 jnp.float32)
    tok = (jnp.arange(slots, dtype=jnp.int32) * 7 + 3) % cfg.vocab
    jd = jax.jit(lambda p, c, t: dec.decode_step(p, c, t, cfg, None,
                                                 fuse=False))
    jf = jax.jit(lambda p, c, t: dec.paged_decode_step(p, c, t, cfg, None,
                                                       fuse=True))
    ju = jax.jit(lambda p, c, t: dec.paged_decode_step(p, c, t, cfg, None,
                                                       fuse=False))
    cd, cf, cu = dense, paged, paged
    for _ in range(6):
        ld, cd = jd(params, cd, tok)
        lf, cf = jf(params, cf, tok)
        lu, cu = ju(params, cu, tok)
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lu))
        np.testing.assert_array_equal(np.asarray(ld), np.asarray(lf))
        tok = jnp.argmax(ld.astype(jnp.float32), axis=-1).astype(jnp.int32)
    # memory accounting: pages allocated == ceil(tokens/page) per slot
    used = int(paged["free_top"]) - int(cf["free_top"])
    assert used == slots * -(-6 // page_size)


def test_paged_heterogeneous_lengths_match_solo_dense():
    """Mixed request lengths in one paged batch (late joiner via the
    active mask): every ACTIVE slot's logits are bit-exact vs a dense
    decode of the same forced token stream run fresh in that slot (same
    batch width, so the compiled program is identical row-for-row)."""
    cfg = _cfg(layers=2, hd=16, scan=False)
    params = init_params(cfg, jax.random.key(1))
    max_len, ps = 16, 4
    paged = dec.init_paged_cache(cfg, 2, max_len, ps, jnp.float32)
    jp = jax.jit(lambda p, c, t, a: dec.paged_decode_step(
        p, c, t, cfg, None, active=a))
    jd = jax.jit(lambda p, c, t: dec.decode_step(p, c, t, cfg, None))

    streams = {0: [5], 1: [11]}        # slot 1 joins at step 2
    joins = {0: 0, 1: 2}
    paged_logits = {0: [], 1: []}
    for step in range(6):
        act = jnp.asarray([joins[s] <= step for s in (0, 1)])
        tok = jnp.asarray([streams[s][-1] if joins[s] <= step else 0
                           for s in (0, 1)], jnp.int32)
        lg, paged = jp(params, paged, tok, act)
        for s in (0, 1):
            if joins[s] <= step:
                paged_logits[s].append(np.asarray(lg[s]))
                streams[s].append(int(jnp.argmax(
                    lg[s].astype(jnp.float32))))
    for s in (0, 1):
        solo = dec.init_cache(cfg, 2, max_len, jnp.float32)
        toks = [[5, 11][s]]
        for want in paged_logits[s]:
            cur = [0, 0]
            cur[s] = toks[-1]
            lg, solo = jd(params, solo, jnp.asarray(cur, jnp.int32))
            np.testing.assert_array_equal(np.asarray(lg[s]), want)
            toks.append(int(jnp.argmax(lg[s].astype(jnp.float32))))


def test_paged_windowed_layers_allclose_vs_dense_ring():
    """Sliding-window layers: paged full-length + attention-time mask vs
    the dense ring buffer — same attended set, different storage order."""
    cfg = _cfg(layers=2, hd=16, scan=True, window=8)
    params = init_params(cfg, jax.random.key(2))
    dense = dec.init_cache(cfg, 2, 32, jnp.float32)
    paged = dec.init_paged_cache(cfg, 2, 32, 4, jnp.float32)
    tok = jnp.asarray([3, 9], jnp.int32)
    jd = jax.jit(lambda p, c, t: dec.decode_step(p, c, t, cfg, None))
    jp = jax.jit(lambda p, c, t: dec.paged_decode_step(p, c, t, cfg, None))
    cd, cp = dense, paged
    for _ in range(12):                # crosses the window boundary at 8
        ld, cd = jd(params, cd, tok)
        lp, cp = jp(params, cp, tok)
        np.testing.assert_allclose(np.asarray(ld, np.float32),
                                   np.asarray(lp, np.float32),
                                   rtol=2e-5, atol=2e-5)
        tok = jnp.argmax(ld.astype(jnp.float32), axis=-1).astype(jnp.int32)


def test_paged_pool_exhaustion_degrades_locally():
    """An empty free stack must never alias a page between slots or push
    free_top negative: starved slots simply stop storing (appends drop,
    table entries stay -1) and reclamation stays exact."""
    cfg = _cfg(layers=2, hd=16, scan=True)
    params = init_params(cfg, jax.random.key(4))
    # 2 slots x 4 logical pages each, but only 2 physical pages
    cache = dec.init_paged_cache(cfg, 2, 8, 2, jnp.float32, num_pages=2)
    jp = jax.jit(lambda p, c, t: dec.paged_decode_step(p, c, t, cfg, None))
    tok = jnp.asarray([3, 9], jnp.int32)
    for _ in range(6):
        lg, cache = jp(params, cache, tok)
        assert np.isfinite(np.asarray(lg, np.float32)).all()
        tok = jnp.argmax(lg.astype(jnp.float32), -1).astype(jnp.int32)
    assert int(cache["free_top"]) == 0          # exhausted, never negative
    table = np.asarray(cache["table"])
    owned = table[table >= 0]
    assert sorted(owned.tolist()) == [0, 1]     # each page has ONE owner
    assert (table[:, 1:] == -1).all()           # starved entries stay -1
    cache = jax.jit(lambda c, s: dec.paged_release_slot(cfg, c, s))(
        cache, jnp.int32(0))
    assert int(cache["free_top"]) == 1          # exactly slot 0's page back


def test_paged_release_then_reuse_is_bit_exact():
    """The reclamation regression: release a slot, admit a new request
    into the SAME physical pages — outputs bit-exact vs a fresh cache."""
    cfg = _cfg(layers=2, hd=16, scan=True)
    params = init_params(cfg, jax.random.key(3))
    jp = jax.jit(lambda p, c, t: dec.paged_decode_step(p, c, t, cfg, None))
    rel = jax.jit(lambda c, s: dec.paged_release_slot(cfg, c, s))

    cache = dec.init_paged_cache(cfg, 1, 16, 4, jnp.float32)
    free0 = int(cache["free_top"])
    tok = jnp.asarray([7], jnp.int32)
    for _ in range(5):
        lg, cache = jp(params, cache, tok)
        tok = jnp.argmax(lg.astype(jnp.float32), -1).astype(jnp.int32)
    cache = rel(cache, jnp.int32(0))
    assert int(cache["free_top"]) == free0          # all pages reclaimed
    assert int(cache["pos"][0]) == 0

    fresh = dec.init_paged_cache(cfg, 1, 16, 4, jnp.float32)
    tok_r = tok_f = jnp.asarray([13], jnp.int32)
    for _ in range(5):
        lr, cache = jp(params, cache, tok_r)
        lf, fresh = jp(params, fresh, tok_f)
        np.testing.assert_array_equal(np.asarray(lr), np.asarray(lf))
        tok_r = jnp.argmax(lr.astype(jnp.float32), -1).astype(jnp.int32)
        tok_f = jnp.argmax(lf.astype(jnp.float32), -1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# jaxpr gates: one fused paged-gather program / one kernel launch per step
# ---------------------------------------------------------------------------

def test_paged_fused_gather_is_one_program_per_step():
    """The fused step collapses all attention leaves' page gathers into
    ONE gather equation (the per-access path pays one per leaf per
    superblock); with the TPU lowering pinned, the whole fused step also
    issues exactly ONE kernel launch, its float32 K|V split, which takes
    the transpose route and so reads no mask operand."""
    # gather accounting on the pure XLA lowering (pallas interpret-mode
    # kernels would add their own internal gather equations)
    cfg_ref = _cfg(layers=4, hd=64, scan=False, impl="ref", positions=2,
                   mlp="none", d_ff=0)
    params = init_params(cfg_ref, jax.random.key(0))
    cache = dec.init_paged_cache(cfg_ref, 2, 64, 16, jnp.float32)
    tok = jnp.asarray([3, 5], jnp.int32)
    gf = _count_gathers(
        lambda p, c, t: dec.paged_decode_step(p, c, t, cfg_ref, None,
                                              fuse=True),
        params, cache, tok)
    gp = _count_gathers(
        lambda p, c, t: dec.paged_decode_step(p, c, t, cfg_ref, None,
                                              fuse=False),
        params, cache, tok)
    # 2 leaves x 2 superblocks of page gathers collapse into ONE, and the
    # in-place append looks its page ids up once per leaf (one take per
    # scatter) instead of once per leaf per superblock
    assert gp - gf == (2 * 2 - 1) + (2 * 2 - 2), (gf, gp)

    cfg = _cfg(layers=4, hd=64, scan=False, impl="pallas", positions=2,
               mlp="none", d_ff=0)

    def fused(p, c, t):
        return dec.paged_decode_step(p, c, t, cfg, None, fuse=True)

    def per_access(p, c, t):
        return dec.paged_decode_step(p, c, t, cfg, None, fuse=False)

    with accessfuse.pinned_kernel_lowering():
        lf, mf = accessfuse.jaxpr_access_counts(fused, params, cache, tok)
    lp, mp = accessfuse.jaxpr_access_counts(per_access, params, cache, tok)
    assert lf == 1 and mf == 0, (lf, mf)
    assert lp >= 4 and mp >= 4, (lp, mp)


def test_paged_plan_cache_steady_state_under_jit():
    """Stepping the jit'd paged decode must not re-miss the plan cache:
    the program key is the page geometry, never the table contents."""
    cfg = _cfg(layers=2, hd=16, scan=True)
    params = init_params(cfg, jax.random.key(0))
    cache = dec.init_paged_cache(cfg, 2, 16, 4, jnp.float32)
    tok = jnp.asarray([3, 5], jnp.int32)
    jp = jax.jit(lambda p, c, t: dec.paged_decode_step(p, c, t, cfg, None))
    _, cache = jp(params, cache, tok)
    warm = vx.PLANS.stats()["misses"]
    for _ in range(4):
        _, cache = jp(params, cache, tok)
    assert vx.PLANS.stats()["misses"] == warm


# ---------------------------------------------------------------------------
# In-place pool writes: bit-identical to the scanned oracle route
# ---------------------------------------------------------------------------

_ROUTE_CASES = {
    # id: (arch smoke config, pool dtype, quantize, scenario, scan_layers)
    "qwen3-float32": ("qwen3-0.6b", jnp.float32, None, "fresh", True),
    "qwen3-float32-unrolled": ("qwen3-0.6b", jnp.float32, None, "fresh",
                               False),
    "qwen3-bfloat16": ("qwen3-0.6b", jnp.bfloat16, None, "fresh", True),
    "qwen3-int8": ("qwen3-0.6b", jnp.float32, "int8", "fresh", True),
    "qwen3-int8-unrolled": ("qwen3-0.6b", jnp.float32, "int8", "fresh",
                            False),
    "qwen3-fp8": ("qwen3-0.6b", jnp.float32, "fp8", "fresh", True),
    "gemma3-windows-float32": ("gemma3-12b", jnp.float32, None, "fresh",
                               True),
    "gemma3-windows-int8": ("gemma3-12b", jnp.bfloat16, "int8", "fresh",
                            True),
    "jamba-hybrid-float32": ("jamba-1.5-large-398b", jnp.float32, None,
                             "fresh", True),
    "jamba-hybrid-int8": ("jamba-1.5-large-398b", jnp.float32, "int8",
                          "fresh", True),
    "qwen3-shared-prefix-float32": ("qwen3-0.6b", jnp.float32, None,
                                    "shared_prefix", True),
    "qwen3-shared-prefix-int8": ("qwen3-0.6b", jnp.float32, "int8",
                                 "shared_prefix", True),
    "qwen3-exhausted-float32": ("qwen3-0.6b", jnp.float32, None,
                                "exhausted", True),
    "qwen3-exhausted-int8": ("qwen3-0.6b", jnp.float32, "int8",
                             "exhausted", True),
}


def _serve_sequence(cfg, params, fuse, dtype, quantize, scenario):
    """Two slots through prefill chunks, decode steps and a verify step,
    every program on one route (``fuse``): returns what each program
    returned besides the cache, the final cache, and the pool writes the
    programs' traces counted.  ``shared_prefix``: slot 1 adopts slot 0's
    first page before its own chunk; ``exhausted``: a four-page pool, so
    the first decode step's page-boundary appends find the free stack
    empty and drop."""
    ps, max_len = 4, 32
    cache = dec.init_paged_cache(
        cfg, 2, max_len, ps, dtype, quantize=quantize,
        num_pages=4 if scenario == "exhausted" else None)
    chunk = jax.jit(lambda p, c, t, s, n: dec.paged_prefill_chunk(
        p, c, t, cfg, None, slot=s, count=n, fuse=fuse))
    step = jax.jit(lambda p, c, t: dec.paged_decode_step(
        p, c, t, cfg, None, fuse=fuse))
    verify = jax.jit(lambda p, c, t, n: dec.paged_verify_step(
        p, c, t, cfg, None, n_draft=n, fuse=fuse))
    dec.POOL_WRITES.clear()
    prompt = jnp.arange(1, 9, dtype=jnp.int32) * 5 % cfg.vocab
    cache = chunk(params, cache, prompt[:4], 0, 4)
    cache = chunk(params, cache, prompt[4:], 0, 3)
    if scenario == "shared_prefix":
        run = jnp.full((max_len // ps,), -1, jnp.int32).at[0].set(
            cache["table"][0, 0])
        cache = dec.paged_adopt_prefix(cfg, cache, 1, run)
        cache = chunk(params, cache, prompt[4:] + 1, 1, 3)
    else:
        cache = chunk(params, cache, prompt[:4] + 2, 1, 4)
        cache = chunk(params, cache, prompt[4:] + 2, 1, 2)
    outs, tok = [], jnp.asarray([7, 11], jnp.int32)
    for _ in range(3):
        logits, cache = step(params, cache, tok)
        outs.append(logits)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    drafts = jnp.stack([tok, (tok + 1) % cfg.vocab, (tok + 2) % cfg.vocab],
                       axis=1)
    *verified, cache = verify(params, cache, drafts,
                              jnp.asarray([3, 2], jnp.int32))
    logits, cache = step(params, cache, verified[1][:, 0])
    return outs + verified + [logits], cache, dec.POOL_WRITES.stats()


@pytest.mark.parametrize("case", list(_ROUTE_CASES))
def test_in_place_pool_writes_match_the_scanned_route(case):
    """The served route writes each program's new beats into the pool
    stack in place (decode and verify: one scatter per pool leaf after the
    layer loop; prefill chunk: the stack rides the scan's carry).  After
    prefill chunks, decode steps and a verify step every leaf of the cache
    — pools, scales, table, free stack, refcounts, positions, recurrent
    state — is bit-identical to the ``fuse=False`` route that scans the
    stack one layer's pool at a time, and so are the logits, tokens and
    commits; each route's traces count only their own pool writes."""
    arch, dtype, quantize, scenario, scan = _ROUTE_CASES[case]
    cfg = dataclasses.replace(get_arch(arch).smoke, scan_layers=scan)
    params = init_params(cfg, jax.random.key(0))
    outs, caches, writes = zip(*(
        _serve_sequence(cfg, params, fuse, dtype, quantize, scenario)
        for fuse in (True, False)))
    leaves = 3 * len(dec._pool_keys(caches[0], [
        i for i, k in enumerate(cfg.block_pattern) if k == "attn"]))
    assert writes == ({"in_place": leaves}, {"scanned": leaves})
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), caches[0], caches[1])
    n = None
    if scenario == "exhausted":
        assert int(caches[0]["free_top"]) == 0
        assert int((caches[0]["table"] >= 0).sum()) == 4
        if quantize is None:
            # from the second step on the appends drop: the per-access
            # float oracle reads its pool after the append, so it attends
            # to zeros where the fused step attends to the fresh beat —
            # their logits differ by design (their caches do not)
            n = 1
    for a, b in list(zip(*outs))[:n]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
