"""Hypothesis property tests for the EARTH shift-network invariants.

The paper's §4.1.4 conflict-free theorem states that the networks route
without collision exactly when the mapping is order-preserving and
separation-monotone. We generate random legal mappings and assert:
  * no conflict flag at any layer,
  * every valid element lands at its target,
  * gather(scatter(x)) round-trips.
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import scg, shiftnet

settings.register_profile("fast", max_examples=60, deadline=None)
settings.load_profile("fast")


@st.composite
def monotone_gather_map(draw, n=64):
    """Random order-preserving, separation-non-increasing mapping.

    Build target positions first (sorted unique), then source positions with
    pairwise separations >= target separations (guarantees the gather
    precondition, incl. shift >= 0 for all elements).
    """
    k = draw(st.integers(min_value=1, max_value=n // 2))
    targets = sorted(draw(st.sets(st.integers(0, n - 1), min_size=k,
                                  max_size=k)))
    sources = [draw(st.integers(targets[0], n - 1 - sum(
        max(targets[i + 1] - targets[i], 1) for i in range(len(targets) - 1))
        if len(targets) > 1 else n - 1))]
    for i in range(1, len(targets)):
        gap_t = targets[i] - targets[i - 1]
        lo = sources[-1] + gap_t
        hi = n - 1
        if lo > hi:
            return ((), ())  # cannot extend without violating separation
        sources.append(draw(st.integers(lo, hi)))
    # enforce shift >= 0 and in-range
    ok = all(s >= t and s < n for s, t in zip(sources, targets))
    return (sources, targets) if ok else ((), ())


@given(monotone_gather_map())
def test_gather_conflict_free_and_exact(mapping):
    sources, targets = mapping
    if not sources:
        return
    n = 64
    payload = jnp.zeros((n,), jnp.int32)
    shift = jnp.zeros((n,), jnp.int32)
    valid = jnp.zeros((n,), bool)
    for s, t in zip(sources, targets):
        payload = payload.at[s].set(1000 + s)
        shift = shift.at[s].set(s - t)
        valid = valid.at[s].set(True)
    res = shiftnet.gather_network(payload, shift, valid)
    assert not bool(res.conflict), (sources, targets)
    out = np.asarray(res.payload)
    vmask = np.asarray(res.valid)
    for s, t in zip(sources, targets):
        assert vmask[t]
        assert out[t] == 1000 + s
    assert vmask.sum() == len(sources)


@st.composite
def monotone_scatter_map(draw, n=64):
    """Order-preserving, separation-non-decreasing mapping (scatter legal)."""
    k = draw(st.integers(min_value=1, max_value=n // 2))
    sources = sorted(draw(st.sets(st.integers(0, n // 2 - 1), min_size=k,
                                  max_size=k)))
    targets = [draw(st.integers(sources[0], n - 1 - sum(
        max(sources[i + 1] - sources[i], 1) for i in range(len(sources) - 1))
        if len(sources) > 1 else n - 1))]
    for i in range(1, len(sources)):
        gap_s = sources[i] - sources[i - 1]
        lo = targets[-1] + gap_s
        if lo > n - 1:
            return ((), ())
        targets.append(draw(st.integers(lo, n - 1)))
    ok = all(t >= s for s, t in zip(sources, targets))
    return (sources, targets) if ok else ((), ())


@given(monotone_scatter_map())
def test_scatter_conflict_free_and_exact(mapping):
    sources, targets = mapping
    if not sources:
        return
    n = 64
    payload = jnp.zeros((n,), jnp.int32)
    shift = jnp.zeros((n,), jnp.int32)
    valid = jnp.zeros((n,), bool)
    for s, t in zip(sources, targets):
        payload = payload.at[s].set(1000 + s)
        shift = shift.at[s].set(t - s)
        valid = valid.at[s].set(True)
    res = shiftnet.scatter_network(payload, shift, valid)
    assert not bool(res.conflict), (sources, targets)
    out = np.asarray(res.payload)
    vmask = np.asarray(res.valid)
    for s, t in zip(sources, targets):
        assert vmask[t]
        assert out[t] == 1000 + s
    assert vmask.sum() == len(sources)


@given(st.integers(1, 16), st.integers(0, 15), st.integers(1, 10))
def test_strided_roundtrip(stride, offset, vl):
    """scatter(gather(window)) restores strided elements exactly."""
    n = 256
    if offset + (vl - 1) * stride + 1 > n:
        return
    window = jnp.arange(n, dtype=jnp.int32) * 7 + 1
    gs, gv = scg.gather_counts(n, stride, offset, vl)
    dense = shiftnet.gather_network(window, gs, gv)
    assert not bool(dense.conflict)
    ss, sv = scg.scatter_counts(n, stride, offset, vl)
    back = shiftnet.scatter_network(dense.payload, ss, sv)
    assert not bool(back.conflict)
    out = np.asarray(back.payload)
    for i in range(vl):
        p = offset + i * stride
        assert out[p] == p * 7 + 1


@given(st.lists(st.booleans(), min_size=1, max_size=128))
def test_compaction_conflict_free(bits):
    mask = jnp.array(bits, dtype=bool)
    n = mask.shape[0]
    data = jnp.arange(n, dtype=jnp.int32) + 1
    shift, valid = scg.compaction_counts(mask)
    res = shiftnet.gather_network(data, shift, valid)
    assert not bool(res.conflict)
    want = np.asarray(data)[np.asarray(mask)]
    got = np.asarray(res.payload)[: len(want)]
    np.testing.assert_array_equal(got, want)


@given(st.lists(st.booleans(), min_size=1, max_size=128))
def test_expansion_inverts_compaction(bits):
    mask = jnp.array(bits, dtype=bool)
    n = mask.shape[0]
    data = (jnp.arange(n, dtype=jnp.int32) + 1) * jnp.asarray(mask, jnp.int32)
    cs, cv = scg.compaction_counts(mask)
    packed = shiftnet.gather_network(data, cs, cv)
    es, ev = scg.expansion_counts(mask)
    restored = shiftnet.scatter_network(packed.payload, es, ev)
    assert not bool(restored.conflict)
    got = np.where(np.asarray(restored.valid), np.asarray(restored.payload), 0)
    np.testing.assert_array_equal(got, np.asarray(data))


@given(st.integers(2, 8), st.integers(1, 32))
def test_segment_field_extraction(fields, m):
    n = fields * m
    aos = jnp.arange(n, dtype=jnp.int32)
    for f in range(fields):
        shift, valid = scg.segment_gather_counts(n, fields, f, m)
        res = shiftnet.gather_network(aos, shift, valid)
        assert not bool(res.conflict)
        np.testing.assert_array_equal(np.asarray(res.payload)[:m],
                                      np.arange(m) * fields + f)


# ---------------------------------------------------------------------------
# Compiled static-plan path (core/shiftplan.py) vs the dynamic-count oracle.
# The dynamic network above IS the oracle; the compiled plans must match it
# exactly — payload, occupancy, and conflict flag — across strides/offsets/
# vl and all segment field counts.
# ---------------------------------------------------------------------------

import itertools

import pytest

from repro.core import lsdo, shiftplan

STRIDES = (1, 2, 3, 4, 7, 8, 16)


@pytest.mark.parametrize("stride", STRIDES)
@pytest.mark.parametrize("offset", (0, 1, 5))
@pytest.mark.parametrize("n", (64, 128))
def test_compiled_gather_matches_dynamic(stride, offset, n):
    vl = (n - 1 - offset) // stride + 1
    for v in {1, max(1, vl // 2), vl}:
        window = jnp.arange(n, dtype=jnp.int32) * 13 + 7
        shift, valid = scg.gather_counts(n, stride, offset, v)
        dyn = shiftnet.gather_network(window, shift, valid)
        plan = shiftplan.gather_plan(n, stride, offset, v)
        out = shiftnet.apply_plan(window, plan)
        # conflict parity: legal strided patterns are conflict-free on both
        assert not bool(dyn.conflict) and not plan.conflict
        np.testing.assert_array_equal(
            np.asarray(dyn.valid), plan.valid)
        np.testing.assert_array_equal(
            np.where(plan.valid, np.asarray(out), 0),
            np.where(np.asarray(dyn.valid), np.asarray(dyn.payload), 0))


@pytest.mark.parametrize("stride", STRIDES)
@pytest.mark.parametrize("offset", (0, 1, 5))
@pytest.mark.parametrize("n", (64, 128))
def test_compiled_scatter_matches_dynamic(stride, offset, n):
    vl = (n - 1 - offset) // stride + 1
    for v in {1, max(1, vl // 2), vl}:
        dense = jnp.arange(n, dtype=jnp.int32) * 3 + 1
        shift, valid = scg.scatter_counts(n, stride, offset, v)
        dyn = shiftnet.scatter_network(dense, shift, valid)
        plan = shiftplan.scatter_plan(n, stride, offset, v)
        out = shiftnet.apply_plan(dense, plan)
        assert not bool(dyn.conflict) and not plan.conflict
        np.testing.assert_array_equal(np.asarray(dyn.valid), plan.valid)
        np.testing.assert_array_equal(
            np.where(plan.valid, np.asarray(out), 0),
            np.where(np.asarray(dyn.valid), np.asarray(dyn.payload), 0))


@pytest.mark.parametrize("fields", (2, 3, 4, 5, 6, 7, 8))
def test_compiled_segment_matches_dynamic(fields):
    m = 32
    n = fields * m
    aos = jnp.arange(n, dtype=jnp.int32) + 100
    plan = shiftplan.deinterleave_plan(n, fields)
    x = jnp.pad(aos, (0, plan.n - n)) if plan.n > n else aos
    routed = np.asarray(shiftnet.apply_plan(x, plan))
    for f in range(fields):
        shift, valid = scg.segment_gather_counts(n, fields, f, m)
        dyn = shiftnet.gather_network(aos, shift, valid)
        assert not bool(dyn.conflict)
        np.testing.assert_array_equal(routed[f * m:(f + 1) * m],
                                      np.asarray(dyn.payload)[:m])
    # and the fused interleave inverts it
    ipl = shiftplan.interleave_plan(n, fields)
    soa = routed[:n]
    xi = np.pad(soa, (0, ipl.n - n)) if ipl.n > n else soa
    back = np.asarray(shiftnet.apply_plan(jnp.asarray(xi), ipl))[:n]
    np.testing.assert_array_equal(back, np.asarray(aos))


def test_stride2_gather_prunes_layers():
    """Acceptance: stride-2 gather over n=128 executes < log2(n) layers."""
    plan = shiftplan.gather_plan(128, 2, 0, 64)
    assert plan.total_layers == 7
    assert plan.active_layers < 7, plan.active_layers
    assert not plan.conflict


def test_single_transaction_patterns_need_few_layers():
    """Unit-stride windows route with ZERO active layers (identity);
    offset-only windows need exactly the popcount of the offset."""
    assert shiftplan.gather_plan(128, 1, 0, 128).active_layers == 0
    p = shiftplan.gather_plan(128, 1, 4, 64)
    assert p.active_layers == 1     # all elements shift by 4 = one bit
    p = shiftplan.gather_plan(128, 1, 5, 64)
    assert p.active_layers == 2     # shift 5 = bits 0 and 2


def test_batched_plan_matches_per_transaction():
    """The (T, mlen) batched LSDO plan equals the per-transaction loop."""
    buf = jnp.arange(1024, dtype=jnp.float32) * 5 + 3
    for base, stride, vl, mlen in [(0, 2, 64, 128), (7, 3, 40, 64),
                                   (5, 16, 30, 128), (1, -4, 50, 64),
                                   (3, 1, 100, 32)]:
        plan = lsdo.plan_strided(base, stride, vl, mlen)
        got = lsdo.load_strided(buf, plan)                  # batched
        want = lsdo.load_strided(buf, plan, batched=False)  # loop oracle
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        vals = jnp.arange(1, vl + 1, dtype=jnp.float32)
        sb = lsdo.store_strided(jnp.zeros(1024), vals, plan)
        sl = lsdo.store_strided(jnp.zeros(1024), vals, plan, batched=False)
        np.testing.assert_array_equal(np.asarray(sb), np.asarray(sl))


def test_permutation_plan_random():
    """Benes fallback routes arbitrary permutations conflict-free."""
    rng = np.random.default_rng(0)
    for n in (8, 32, 57, 128):
        perm = rng.permutation(n)
        plan = shiftplan.permutation_plan(tuple(int(x) for x in perm))
        x = np.pad(np.arange(n), (0, plan.n - n))
        out = shiftplan.apply_np(plan, x)
        for src, dst in enumerate(perm):
            assert out[dst] == src
        assert plan.active_layers <= 2 * shiftplan.num_layers(plan.n) - 1


def test_compiled_counts_plan_matches_dynamic_counts():
    """Static host-side (shift, valid) through counts_plan == dynamic net."""
    rng = np.random.default_rng(1)
    n = 64
    for _ in range(10):
        k = int(rng.integers(1, n // 2))
        targets = np.sort(rng.choice(n, size=k, replace=False))
        # order-preserving, separation-non-increasing sources
        sources = targets.copy()
        slack = n - 1 - targets[-1]
        sources = targets + rng.integers(0, slack + 1)
        shift = np.zeros(n, np.int64)
        valid = np.zeros(n, bool)
        for s, t in zip(sources, targets):
            shift[s] = s - t
            valid[s] = True
        plan = shiftplan.counts_plan(tuple(int(x) for x in shift),
                                     tuple(bool(v) for v in valid),
                                     gather=True)
        dyn = shiftnet.gather_network(jnp.arange(n), jnp.asarray(shift),
                                      jnp.asarray(valid))
        assert plan.conflict == bool(dyn.conflict) == False  # noqa: E712
        out = shiftplan.apply_np(plan, np.arange(n))
        np.testing.assert_array_equal(
            np.where(plan.valid, out, 0),
            np.where(np.asarray(dyn.valid), np.asarray(dyn.payload), 0))


def test_segment_strategy_cost_model():
    """The segment compiler picks per-field compiled passes when they are
    cheaper and the FUSED single-pass bulk transposition for wide segments;
    either choice must cost no more wide ops than the seed's dynamic path
    (fields passes x log2(n) layers x 3 shifted arrays each)."""
    for fields, m in [(2, 128), (4, 128), (8, 128), (32, 8)]:
        n = fields * m
        mode, plans = shiftplan.segment_deinterleave_plans(n, fields)
        cost = sum(p.wide_ops for p in plans)
        seed_cost = fields * shiftplan.num_layers(n) * 3
        assert cost < seed_cost, (fields, m, mode, cost, seed_cost)
        # correctness of the chosen strategy via the host-side applier
        x = np.arange(n)
        if mode == "fused":
            assert len(plans) == 1      # ONE pass handles all fields
            plan = plans[0]
            out = shiftplan.apply_np(plan, np.pad(x, (0, plan.n - n)))[:n]
            np.testing.assert_array_equal(
                out, x.reshape(m, fields).T.reshape(-1))
        else:
            for f, plan in enumerate(plans):
                out = shiftplan.apply_np(plan, x)
                np.testing.assert_array_equal(out[:m],
                                              np.arange(m) * fields + f)
    # wide segments fuse into a single O(log n) pass
    mode, plans = shiftplan.segment_deinterleave_plans(256, 32)
    assert mode == "fused" and len(plans) == 1
    assert plans[0].active_layers <= 2 * shiftplan.num_layers(plans[0].n) - 1


@pytest.mark.parametrize("stride", (-1, -2, -3, -4, -7, -8))
def test_reverser_negative_stride_load_store(stride):
    """§3.2.2 Reverser: negative strides plan on the reversed element order
    and un-reverse the assembled output — batched and loop paths both must
    match direct indexing, and store must invert load."""
    n = 512
    buf = jnp.arange(n, dtype=jnp.float32) * 3 + 1
    base, vl, mlen = 400, 40, 64
    plan = lsdo.plan_strided(base, stride, vl, mlen)
    assert plan.reversed
    want = np.asarray([3 * (base + i * stride) + 1 for i in range(vl)],
                      np.float32)
    for batched in (True, False):
        got = np.asarray(lsdo.load_strided(buf, plan, batched=batched))
        np.testing.assert_array_equal(got, want, err_msg=f"{batched=}")
        vals = jnp.arange(1, vl + 1, dtype=jnp.float32)
        out = np.asarray(lsdo.store_strided(jnp.zeros(n), vals, plan,
                                            batched=batched))
        for i in range(vl):
            assert out[base + i * stride] == i + 1
        assert np.count_nonzero(out) == vl


# ---------------------------------------------------------------------------
# Runtime-stride plan bank (core/accessfuse.py): lax.switch dispatch over
# compiled plans must match the dynamic oracle bit-exactly — every banked
# stride (±1..8), both signs (Reverser), and the out-of-bank fallback.
# ---------------------------------------------------------------------------

from repro.core import accessfuse

BANK_SWEEP = tuple(range(1, 9)) + tuple(-s for s in range(1, 9)) + (9, -9)


@pytest.mark.parametrize("stride", BANK_SWEEP)
def test_plan_bank_gather_matches_dynamic_oracle(stride):
    n, offset, vl = 128, 64, 8
    win = jnp.arange(n, dtype=jnp.int32) * 13 + 7
    win2 = jnp.broadcast_to(win, (4, n))
    traced = jax.jit(lambda w, s: accessfuse.bank_gather_strided(
        w, s, offset, vl))(win2, jnp.int32(stride))
    static = accessfuse.bank_gather_strided(win2, stride, offset, vl)
    want = np.asarray(win)[offset + stride * np.arange(vl)]
    np.testing.assert_array_equal(np.asarray(traced),
                                  np.broadcast_to(want, (4, vl)))
    np.testing.assert_array_equal(np.asarray(static), np.asarray(traced))


@pytest.mark.parametrize("stride", BANK_SWEEP)
def test_plan_bank_scatter_matches_dynamic_oracle(stride):
    n, offset, vl = 128, 64, 8
    vals = jnp.broadcast_to(jnp.arange(1, vl + 1, dtype=jnp.int32), (4, vl))
    base = jnp.zeros((4, n), jnp.int32)
    traced = jax.jit(lambda w, v, s: accessfuse.bank_scatter_strided(
        w, v, s, offset))(base, vals, jnp.int32(stride))
    static = accessfuse.bank_scatter_strided(base, vals, stride, offset)
    want = np.zeros(n, np.int64)
    want[offset + stride * np.arange(vl)] = np.arange(1, vl + 1)
    np.testing.assert_array_equal(np.asarray(traced),
                                  np.broadcast_to(want, (4, n)))
    np.testing.assert_array_equal(np.asarray(static), np.asarray(traced))


def test_plan_bank_unfittable_slot_routes_to_fallback():
    """A banked stride whose (offset, vl) does not fit the window must
    still produce oracle results via the dynamic branch."""
    n, offset, vl = 64, 0, 16
    win = jnp.arange(n, dtype=jnp.int32)
    # stride 8 needs offset + 15*8 = 120 >= n: slot is None -> fallback...
    # for an in-range request we must pick a stride that fits; stride 5
    # (75 >= 64) is also unfittable, so sweep only fitting ones and assert
    # the bank builder marked non-fitting slots None.
    slots = accessfuse._gather_bank(n, offset, vl)
    assert slots[7] is None and slots[4] is None       # strides 8 and 5
    for stride in (1, 2, 3, 4):
        got = jax.jit(lambda w, s: accessfuse.bank_gather_strided(
            w, s, offset, vl))(win, jnp.int32(stride))
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(win)[::stride][:vl])


def test_multi_access_plan_matches_batched_plans():
    """The whole-step multi-access plan (concatenated transactions of
    several accesses) routes identically to per-access batched plans."""
    mlen = 64
    accesses = [(2, ((0, 10), (3, 20))), (4, ((1, 8), (5, 12))),
                (1, ((0, 64),))]
    rows = tuple((s, o, c) for s, pairs in accesses for o, c in pairs)
    mplan = shiftplan.multi_gather_plan(mlen, rows)
    assert not mplan.conflict
    x = np.arange(len(rows) * mlen).reshape(len(rows), mlen)
    got = shiftplan.apply_np(mplan, x)
    r = 0
    for s, pairs in accesses:
        bplan = shiftplan.batched_gather_plan(
            mlen, s, tuple(o for o, _ in pairs), tuple(c for _, c in pairs))
        want = shiftplan.apply_np(bplan, x[r:r + len(pairs)])
        valid = bplan.valid
        np.testing.assert_array_equal(np.where(valid, got[r:r + len(pairs)], 0),
                                      np.where(valid, want, 0))
        np.testing.assert_array_equal(mplan.valid[r:r + len(pairs)], valid)
        r += len(pairs)


def test_lsdo_region_past_buffer_end():
    """A transaction whose aligned region hangs past the buffer end must
    still load/store the in-bounds strided elements exactly (per-lane
    clipping; a start-clamped dynamic_slice would shift the window)."""
    buf = jnp.arange(100, dtype=jnp.float32)
    plan = lsdo.plan_strided(30, 3, 20, 64)   # elements 30..87, region 1
    want = np.asarray([30 + 3 * i for i in range(20)], np.float32)
    for batched in (True, False):
        got = np.asarray(lsdo.load_strided(buf, plan, batched=batched))
        np.testing.assert_array_equal(got, want, err_msg=f"{batched=}")
        vals = jnp.arange(1, 21, dtype=jnp.float32)
        out = np.asarray(lsdo.store_strided(jnp.zeros(100), vals, plan,
                                            batched=batched))
        np.testing.assert_array_equal(out[30:88:3], np.asarray(vals))
        assert out.shape == (100,)
