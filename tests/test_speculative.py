"""Speculative K-token decode (PR 10): one fused page-gather/verify program.

Decode level — ``paged_verify_step`` commits exactly the non-speculative
oracle's stream: K=1 degenerates to the single-token step bit-exact
(logits included), accept-all catches up K tokens per launch, reject-all
commits one per launch, and a rejection exactly on a page boundary hands
the speculatively-allocated page straight back to the free stack (page
table + per-slot pos are the ONLY rollback state).  Windowed and
recurrent mixes track the oracle under the allclose contract; the int8
pool stays invariant-green with bounded-error divergence allowed.

Lowering level — the verify program keeps the fused-step shape: fusing
removes the same three gather equations as the single-token step on the
2-superblock x 2-position ref cfg, lowers to ONE pinned pallas launch +
ONE mask program, and the plan cache takes ZERO steady-state misses
across mixed per-slot ``n_draft`` (the verify width is static; per-slot
effective widths are traced operands).

Serve level — the speculative scheduler's token streams are bit-exact vs
the plain scheduler: uniform K, mixed per-request K, ``max_new_tokens``
clamping (a K-wide commit must not overshoot the budget by K-1),
preempt-resume replay THROUGH the verify batch (recorded tokens are
perfect drafts), and prefix sharing with the refcount audit on.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import vx
from repro.core import accessfuse
from repro.models import decode as dec
from repro.models.transformer import ModelConfig, init_params
from repro.serve.scheduler import Scheduler


def _cfg(layers=2, hd=16, scan=True, impl="ref", pattern=("attn",),
         window=None, mlp="swiglu", d_ff=64, name="spec-test"):
    n = len(pattern)
    kw = {}
    if "mamba" in pattern:
        from repro.models.ssm import MambaSpec
        kw["mamba"] = MambaSpec(d_model=2 * hd)
    return ModelConfig(
        name=name, d_model=2 * hd, n_layers=layers, n_heads=2,
        n_kv_heads=2, d_ff=d_ff, vocab=97, head_dim=hd, mlp=mlp,
        block_pattern=pattern, window_pattern=(window,) * n,
        moe_pattern=(False,) * n,
        scan_layers=scan, kernel_impl=impl, remat="none", **kw)


def _jits(cfg):
    jd = jax.jit(lambda p, c, t, a: dec.paged_decode_step(
        p, c, t, cfg, None, active=a))
    jv = jax.jit(lambda p, c, t, n, a: dec.paged_verify_step(
        p, c, t, cfg, None, n_draft=n, active=a))
    return jd, jv


def _oracle(cfg, params, jd, slots, ps, max_len, steps):
    """Greedy single-token streams + per-step logits (the ground truth)."""
    oc = dec.init_paged_cache(cfg, slots, max_len, ps, jnp.float32)
    act = jnp.ones((slots,), bool)
    cur = (jnp.arange(slots, dtype=jnp.int32) * 7 + 3) % cfg.vocab
    stream = [[int(cur[s])] for s in range(slots)]
    logits = [[] for _ in range(slots)]
    for _ in range(steps):
        lg, oc = jd(params, oc, cur, act)
        cur = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        for s in range(slots):
            stream[s].append(int(cur[s]))
            logits[s].append(np.asarray(lg[s]))
    return stream, logits


def _spec_replay(cfg, params, jv, oracle, K, steps, slots, ps, max_len,
                 corrupt_at=frozenset(), check_logits=True):
    """Drive paged_verify_step with oracle-perfect drafts (optionally
    corrupted at (round, slot, j) to force rejections) and return the
    committed streams plus the per-round commit counts."""
    sc = dec.init_paged_cache(cfg, slots, max_len, ps, jnp.float32)
    act = jnp.ones((slots,), bool)
    stream = [[oracle[0][s][0]] for s in range(slots)]
    nd = jnp.full((slots,), K, jnp.int32)
    commits = []
    rnd = 0
    while min(len(t) for t in stream) < steps and rnd < 80:
        toks = np.zeros((slots, K), np.int32)
        for s in range(slots):
            fed = len(stream[s])
            toks[s, 0] = stream[s][-1]
            for j in range(1, K):
                t = oracle[0][s][fed - 1 + j]
                if (rnd, s, j) in corrupt_at:
                    t = (t + 1) % cfg.vocab
                toks[s, j] = t
        lg, o, commit, sc = jv(params, sc, jnp.asarray(toks), nd, act)
        o, cm = np.asarray(o), np.asarray(commit)
        commits.append([int(c) for c in cm])
        for s in range(slots):
            fed = len(stream[s])
            for j in range(int(cm[s])):
                stream[s].append(int(o[s, j]))
                if check_logits:
                    # committed logits track the oracle's to float32
                    # reduction-order tolerance (the K-wide batch shape
                    # changes XLA's contraction order); the TOKEN stream
                    # is the bit-exact contract
                    np.testing.assert_allclose(
                        np.asarray(lg[s, j]), oracle[1][s][fed - 1 + j],
                        rtol=5e-4, atol=1e-5)
        assert not dec.paged_invariants(cfg, sc), \
            dec.paged_invariants(cfg, sc)
        rnd += 1
    return stream, commits, sc


def _streams_equal(spec, oracle, steps):
    for s, (a, b) in enumerate(zip(spec, oracle)):
        n = min(steps, len(a))
        assert a[:n] == b[:n], f"slot {s}: {a[:n]} != {b[:n]}"


# ---------------------------------------------------------------------------
# decode level


def test_k1_degenerates_to_single_step_bit_exact():
    """The width-1 step is the paged decode step: fused, it reproduces
    the ``fuse=False`` oracle bit for bit — logits, argmax tokens,
    commits and every cache leaf — with an idle slot on some steps."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.key(0))
    jd, jv = _jits(cfg)
    jo = jax.jit(lambda p, c, t, n, a: dec.paged_verify_step(
        p, c, t, cfg, None, n_draft=n, active=a, fuse=False))
    slots, ps, max_len = 2, 4, 32
    dc = dec.init_paged_cache(cfg, slots, max_len, ps, jnp.float32)
    vc = dec.init_paged_cache(cfg, slots, max_len, ps, jnp.float32)
    oc = dec.init_paged_cache(cfg, slots, max_len, ps, jnp.float32)
    nd = jnp.ones((slots,), jnp.int32)
    cur = (jnp.arange(slots, dtype=jnp.int32) * 7 + 3) % cfg.vocab
    for step in range(8):
        act = jnp.asarray([True, step % 3 != 1])
        lg_d, dc = jd(params, dc, cur, act)
        lg_v, o, cm, vc = jv(params, vc, cur[:, None], nd, act)
        lg_o, o_o, cm_o, oc = jo(params, oc, cur[:, None], nd, act)
        np.testing.assert_array_equal(np.asarray(lg_v), np.asarray(lg_o))
        np.testing.assert_array_equal(np.asarray(lg_v[:, 0]),
                                      np.asarray(lg_d))
        np.testing.assert_array_equal(np.asarray(o), np.asarray(o_o))
        np.testing.assert_array_equal(np.asarray(cm), np.asarray(cm_o))
        assert np.asarray(cm).tolist() == np.asarray(act, int).tolist()
        for c in (vc, dc):
            jax.tree.map(lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), c, oc)
        cur = jnp.where(act, o[:, 0], cur)


def test_accept_all_catches_oracle_k_per_launch():
    cfg = _cfg()
    params = init_params(cfg, jax.random.key(0))
    jd, jv = _jits(cfg)
    K, steps, slots, ps, max_len = 4, 12, 2, 4, 64
    oracle = _oracle(cfg, params, jd, slots, ps, max_len, steps + K + 2)
    stream, commits, _ = _spec_replay(cfg, params, jv, oracle, K, steps,
                                      slots, ps, max_len)
    _streams_equal(stream, oracle[0], steps)
    # perfect drafts: every verify commits the full width
    assert all(c == K for row in commits[:-1] for c in row), commits


def test_reject_all_commits_one_per_launch():
    cfg = _cfg()
    params = init_params(cfg, jax.random.key(0))
    jd, jv = _jits(cfg)
    K, steps, slots, ps, max_len = 4, 10, 2, 4, 64
    oracle = _oracle(cfg, params, jd, slots, ps, max_len, steps + K + 2)
    corrupt = {(r, s, 1) for r in range(80) for s in range(slots)}
    stream, commits, _ = _spec_replay(cfg, params, jv, oracle, K, steps,
                                      slots, ps, max_len,
                                      corrupt_at=corrupt)
    _streams_equal(stream, oracle[0], steps)
    # first draft always wrong: the head token is the only commit
    assert all(c == 1 for row in commits for c in row), commits


def test_mixed_rejections_track_oracle():
    cfg = _cfg()
    params = init_params(cfg, jax.random.key(0))
    jd, jv = _jits(cfg)
    K, steps, slots, ps, max_len = 4, 12, 2, 4, 64
    oracle = _oracle(cfg, params, jd, slots, ps, max_len, steps + K + 2)
    stream, _, _ = _spec_replay(
        cfg, params, jv, oracle, K, steps, slots, ps, max_len,
        corrupt_at={(0, 0, 1), (1, 1, 2), (2, 0, 3), (4, 1, 1)})
    _streams_equal(stream, oracle[0], steps)


def test_rejection_on_page_boundary_returns_page_to_free_stack():
    """Slot sits one token before a page boundary; the K-wide verify
    speculatively appends across it (allocating a fresh page inside the
    jit) but every draft is rejected — commit lands EXACTLY on the
    boundary.  The overflow page must come straight back: the free stack
    is unchanged and the invariant audit stays green."""
    cfg = _cfg()
    params = init_params(cfg, jax.random.key(0))
    jd, jv = _jits(cfg)
    K, slots, ps, max_len = 4, 1, 4, 32
    oracle = _oracle(cfg, params, jd, slots, ps, max_len, 2 * ps + K + 2)
    sc = dec.init_paged_cache(cfg, slots, max_len, ps, jnp.float32)
    act = jnp.ones((slots,), bool)
    # single-token steps up to pos == ps - 1 (one before the boundary)
    cur = jnp.asarray([oracle[0][0][0]], jnp.int32)
    for i in range(ps - 1):
        lg, sc = jd(params, sc, cur, act)
        cur = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    assert int(np.asarray(sc["pos"])[0]) == ps - 1
    free_before = int(sc["free_top"])
    # head + K-1 corrupted drafts: commit == 1 -> pos == ps exactly;
    # the verify wrote positions ps..ps+K-2 into a freshly-allocated
    # page that the rollback must return
    toks = np.zeros((slots, K), np.int32)
    toks[0, 0] = int(cur[0])
    fed = ps
    for j in range(1, K):
        toks[0, j] = (oracle[0][0][fed - 1 + j] + 1) % cfg.vocab
    lg, o, cm, sc = jv(params, sc, jnp.asarray(toks),
                       jnp.full((slots,), K, jnp.int32), act)
    assert int(np.asarray(cm)[0]) == 1
    assert int(np.asarray(sc["pos"])[0]) == ps
    assert int(sc["free_top"]) == free_before, \
        "rolled-back page did not return to the free stack"
    assert not dec.paged_invariants(cfg, sc), dec.paged_invariants(cfg, sc)
    # committed token still the oracle's
    assert int(np.asarray(o)[0, 0]) == oracle[0][0][ps]


def _allclose_replay(cfg, K=3, steps=10, slots=2, ps=4, max_len=64,
                     quantize=None):
    """Stream-tracking harness for allclose-contract stacks: returns the
    number of slots whose committed stream diverged from the oracle."""
    params = init_params(cfg, jax.random.key(0))
    jd, jv = _jits(cfg)
    oc = dec.init_paged_cache(cfg, slots, max_len, ps, jnp.float32,
                              quantize=quantize)
    act = jnp.ones((slots,), bool)
    cur = (jnp.arange(slots, dtype=jnp.int32) * 7 + 3) % cfg.vocab
    ostream = [[int(cur[s])] for s in range(slots)]
    for _ in range(steps + K + 2):
        lg, oc = jd(params, oc, cur, act)
        cur = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        for s in range(slots):
            ostream[s].append(int(cur[s]))
    sc = dec.init_paged_cache(cfg, slots, max_len, ps, jnp.float32,
                              quantize=quantize)
    sstream = [[ostream[s][0]] for s in range(slots)]
    nd = jnp.asarray([K, max(1, K - 1)], jnp.int32)[:slots]
    rnd = 0
    while min(len(t) for t in sstream) < steps and rnd < 60:
        toks = np.zeros((slots, K), np.int32)
        for s in range(slots):
            fed = len(sstream[s])
            toks[s, 0] = sstream[s][-1]
            for j in range(1, K):
                toks[s, j] = ostream[s][fed - 1 + j] \
                    if fed - 1 + j < len(ostream[s]) else 0
        lg, o, commit, sc = jv(params, sc, jnp.asarray(toks), nd, act)
        o, cm = np.asarray(o), np.asarray(commit)
        for s in range(slots):
            for j in range(int(cm[s])):
                sstream[s].append(int(o[s, j]))
        assert not dec.paged_invariants(cfg, sc), \
            dec.paged_invariants(cfg, sc)
        rnd += 1
    mism = 0
    for s in range(slots):
        n = min(steps, len(sstream[s]))
        if sstream[s][:n] != ostream[s][:n]:
            mism += 1
    return mism


def test_windowed_stream_tracks_oracle():
    assert _allclose_replay(_cfg(window=8), K=3, steps=12, max_len=32) == 0


def test_recurrent_mix_stream_tracks_oracle():
    assert _allclose_replay(_cfg(pattern=("attn", "mamba")),
                            K=3, steps=10) == 0


def test_int8_pool_invariant_green_under_speculation():
    # bounded-error contract: the int8 stream MAY diverge from the f32
    # oracle; the gate is that rollback keeps the quantized pool's
    # invariants (scale liveness included) green every round
    _allclose_replay(_cfg(), K=3, steps=10, quantize="int8")


# ---------------------------------------------------------------------------
# lowering level


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


def _gate_cfg(impl):
    return _cfg(layers=4, hd=64, scan=False, impl=impl,
                pattern=("attn", "attn"), mlp="none", d_ff=0,
                name=f"spec-gate-{impl}")


def _scatter_operand_shapes(fn, *args) -> list[tuple]:
    """Operand shapes of every `scatter` equation anywhere in the jaxpr."""
    def rec(jaxpr):
        out = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scatter":
                out.append(tuple(eqn.invars[0].aval.shape))
            for v in eqn.params.values():
                for sub in accessfuse._child_jaxprs(v):
                    out += rec(sub)
        return out
    return rec(jax.make_jaxpr(lambda *a: fn(*a))(*args).jaxpr)


@pytest.mark.parametrize("width", (1, 4))
def test_verify_fuses_page_gathers_ref(width):
    """Fusing the width-K step removes the same three page-table gathers
    at every width on the 2sb x 2pos cfg — K stacks along the beat axis
    of ONE vx.Paged spec, it does not multiply gather programs — and the
    fresh beats enter the pre-split K and V by select: no scatter writes
    a (B, S, Kh, D) array."""
    cfg = _gate_cfg("ref")
    params = init_params(cfg, jax.random.key(0))
    cache = dec.init_paged_cache(cfg, 2, 64, 16, jnp.float32)
    toks = jnp.zeros((2, width), jnp.int32)
    nd = jnp.full((2,), width, jnp.int32)

    def step(fuse):
        return lambda p, c, t, n: dec.paged_verify_step(
            p, c, t, cfg, None, n_draft=n, fuse=fuse)
    gf = _count_gathers(step(True), params, cache, toks, nd)
    gp = _count_gathers(step(False), params, cache, toks, nd)
    # three page gathers fewer, and the in-place append looks its page ids
    # up once per leaf (one take per scatter), not once per leaf per
    # superblock
    assert gp - gf == 3 + 2, (gf, gp)
    pre_split = (2, 64, cfg.n_kv_heads, cfg.hd)
    shapes = _scatter_operand_shapes(step(True), params, cache, toks, nd)
    assert pre_split not in shapes, shapes


def test_verify_single_pinned_launch_pallas():
    cfg = _gate_cfg("pallas")
    params = init_params(cfg, jax.random.key(0))
    cache = dec.init_paged_cache(cfg, 2, 64, 16, jnp.float32)
    toks = jnp.zeros((2, 4), jnp.int32)
    nd = jnp.full((2,), 4, jnp.int32)
    with accessfuse.pinned_kernel_lowering():
        launches, masks = accessfuse.jaxpr_access_counts(
            lambda p, c, t, n: dec.paged_verify_step(
                p, c, t, cfg, None, n_draft=n, fuse=True),
            params, cache, toks, nd)
    # the one launch is the merged float32 K|V split, whose whole 128-row
    # chunks take the transpose route: no mask operand
    assert (launches, masks) == (1, 0), (launches, masks)


def test_plans_steady_across_mixed_n_draft():
    cfg = _cfg()
    params = init_params(cfg, jax.random.key(0))
    cache = dec.init_paged_cache(cfg, 2, 16, 4, jnp.float32)
    jv = jax.jit(lambda p, c, t, n: dec.paged_verify_step(
        p, c, t, cfg, None, n_draft=n))
    toks = jnp.zeros((2, 4), jnp.int32)
    _, _, _, cache = jv(params, cache, toks, jnp.asarray([4, 4], jnp.int32))
    warm = vx.PLANS.stats()["misses"]
    for nd_ in ([1, 4], [2, 3], [4, 1], [3, 3]):
        _, _, _, cache = jv(params, cache, toks,
                            jnp.asarray(nd_, jnp.int32))
    assert vx.PLANS.stats()["misses"] == warm, \
        "plan cache missed across mixed per-slot verify widths"


# ---------------------------------------------------------------------------
# serve level


_PROMPTS = [[3, 5, 7, 11, 13], [2, 4], [17, 19, 23, 29, 31, 37, 41, 2, 3]]


def _sched_pair():
    cfg = _cfg(layers=2, hd=16)
    dcfg = _cfg(layers=1, hd=8, name="spec-draft")
    params = init_params(cfg, jax.random.key(0))
    dparams = init_params(dcfg, jax.random.key(1))
    return cfg, params, dcfg, dparams


def _drain(sched, reqs, ticks=120):
    for _ in range(ticks):
        sched.tick()
        if sched.drained():
            return
    raise AssertionError("scheduler did not drain")


def _plain_streams(cfg, params, max_new=12, **kw):
    so = Scheduler(cfg, params, slots=3, max_len=64, page_size=4,
                   debug_invariants=True, **kw)
    ro = [so.submit(p, max_new_tokens=max_new) for p in _PROMPTS]
    _drain(so, ro)
    assert all(r.state.value == "finished" for r in ro)
    return [list(r.tokens) for r in ro]


def test_scheduler_stream_equality_uniform_k():
    cfg, params, dcfg, dparams = _sched_pair()
    dec.POOL_WRITES.clear()
    oracle = _plain_streams(cfg, params)
    ss = Scheduler(cfg, params, slots=3, max_len=64, page_size=4,
                   speculate=4, draft_cfg=dcfg, draft_params=dparams,
                   debug_invariants=True)
    rs = [ss.submit(p, max_new_tokens=12) for p in _PROMPTS]
    _drain(ss, rs)
    assert [list(r.tokens) for r in rs] == oracle
    # the served programs (target chunk, step and verify; the draft's
    # chunk and step) write every pool leaf in place
    assert set(dec.POOL_WRITES.stats()) == {"in_place"}
    st = ss.stats()
    assert st["speculative"]["proposed"] > 0
    assert st["speculative"]["accepted"] > 0
    assert {"ttft_p50_s", "ttft_p99_s", "itl_p50_s",
            "itl_p99_s"} <= set(st["latency"])


def test_scheduler_mixed_per_request_k():
    cfg, params, dcfg, dparams = _sched_pair()
    oracle = _plain_streams(cfg, params)
    ss = Scheduler(cfg, params, slots=3, max_len=64, page_size=4,
                   speculate=4, draft_cfg=dcfg, draft_params=dparams,
                   debug_invariants=True)
    rs = [ss.submit(_PROMPTS[0], max_new_tokens=12, speculate=1),
          ss.submit(_PROMPTS[1], max_new_tokens=12, speculate=2),
          ss.submit(_PROMPTS[2], max_new_tokens=12)]
    _drain(ss, rs)
    assert [list(r.tokens) for r in rs] == oracle


def test_scheduler_no_overshoot_of_max_new_tokens():
    """A K-wide commit must stop exactly at the budget — budgets not
    divisible by K previously overshot by up to K-1 tokens."""
    cfg, params, dcfg, dparams = _sched_pair()
    for budget in (5, 7, 10):
        oracle = _plain_streams(cfg, params, max_new=budget)
        ss = Scheduler(cfg, params, slots=3, max_len=64, page_size=4,
                       speculate=4, draft_cfg=dcfg, draft_params=dparams,
                       debug_invariants=True)
        rs = [ss.submit(p, max_new_tokens=budget) for p in _PROMPTS]
        _drain(ss, rs)
        assert [r.generated for r in rs] == [budget] * len(rs)
        assert [list(r.tokens) for r in rs] == oracle


def test_scheduler_preempt_resume_replays_through_verify():
    cfg, params, dcfg, dparams = _sched_pair()
    oracle = _plain_streams(cfg, params)
    ss = Scheduler(cfg, params, slots=1, max_len=64, page_size=4,
                   speculate=4, draft_cfg=dcfg, draft_params=dparams,
                   debug_invariants=True)
    r = ss.submit(_PROMPTS[0], max_new_tokens=12)
    for _ in range(3):
        ss.tick()
    ss.preempt(0)
    _drain(ss, [r])
    assert list(r.tokens) == oracle[0]
    assert r.preemptions == 1


def test_scheduler_prefix_sharing_under_speculation():
    """Shared multi-page prefix + speculation: borrowers adopt the
    donor's pages, verify/rollback runs over shared tables with the
    refcount audit on every tick, and the streams match the plain
    prefix-sharing scheduler exactly."""
    cfg, params, dcfg, dparams = _sched_pair()
    shared = [5, 9, 2, 7, 1, 8, 3, 6]            # two full pages at ps=4
    prompts = [shared + [11], shared + [13], shared + [17]]

    def drive(**kw):
        s = Scheduler(cfg, params, slots=3, max_len=64, page_size=4,
                      prefix_cache=True, debug_invariants=True, **kw)
        reqs = [s.submit(prompts[0], max_new_tokens=10)]
        for _ in range(4):                        # let the donor publish
            s.tick()
        reqs += [s.submit(p, max_new_tokens=10) for p in prompts[1:]]
        _drain(s, reqs)
        assert all(r.state.value == "finished" for r in reqs)
        return [list(r.tokens) for r in reqs], s.stats()

    plain, _ = drive()
    spec, st = drive(speculate=4, draft_cfg=dcfg, draft_params=dparams)
    assert spec == plain
    assert st["prefix"]["tokens_reused"] > 0
    assert st["speculative"]["accepted"] > 0
