"""LSDO planner + RCVRF layout invariants (unit + Hypothesis property)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import lsdo, rcvrf

settings.register_profile("fast2", max_examples=60, deadline=None)
settings.load_profile("fast2")


# ----------------------------- LSDO -----------------------------------------

@given(st.integers(0, 40), st.integers(-12, 12), st.integers(1, 24),
       st.sampled_from([16, 32, 64]))
def test_lsdo_plan_and_load_exact(base, stride, vl, mlen):
    if stride == 0:
        stride = 1
    lo = base + min(0, (vl - 1) * stride)
    hi = base + max(0, (vl - 1) * stride)
    if lo < 0 or hi >= 512 - mlen:
        return
    buf = jnp.arange(512, dtype=jnp.float32) * 3 + 2
    plan = lsdo.plan_strided(base, stride, vl, mlen)
    out = np.asarray(lsdo.load_strided(buf, plan))
    want = np.array([(base + i * stride) * 3 + 2 for i in range(vl)],
                    dtype=np.float32)
    np.testing.assert_array_equal(out, want)


@given(st.integers(0, 40), st.integers(-12, 12), st.integers(1, 24),
       st.sampled_from([16, 32, 64]))
def test_lsdo_store_then_load_roundtrip(base, stride, vl, mlen):
    if stride == 0:
        stride = 1
    lo = base + min(0, (vl - 1) * stride)
    hi = base + max(0, (vl - 1) * stride)
    if lo < 0 or hi >= 512 - mlen:
        return
    # strided elements must be distinct addresses
    vals = jnp.arange(1, vl + 1, dtype=jnp.float32) * 11
    plan = lsdo.plan_strided(base, stride, vl, mlen)
    buf = lsdo.store_strided(jnp.zeros(512), vals, plan)
    out = np.asarray(lsdo.load_strided(buf, plan))
    np.testing.assert_array_equal(out, np.asarray(vals))


@given(st.integers(0, 100), st.integers(1, 20), st.integers(1, 32),
       st.sampled_from([16, 32, 64, 128]))
def test_lsdo_transaction_count_optimal(base, stride, vl, mlen):
    """Coalescing is optimal: #transactions == #distinct aligned regions."""
    plan = lsdo.plan_strided(base, stride, vl, mlen)
    regions = {(base + i * stride) // mlen for i in range(vl)}
    assert plan.num_transactions == len(regions)
    assert plan.coalescing_factor == vl / len(regions)


def test_lsdo_paper_headline_case():
    """EARTH §3.1: 32 x 1-elem stride-2 loads within one 64-elem region -> 1."""
    plan = lsdo.plan_strided(0, 2, 32, 64)
    assert plan.num_transactions == 1
    assert plan.element_wise_transactions == 32


def test_lsdo_segment_planning():
    plans = lsdo.plan_segment_unit(base=0, fields=4, vl=16, mlen=64)
    co, ew = lsdo.transactions_saved(plans)
    assert ew == 64
    assert co == 4  # each field covers 64 elems = exactly one region


# ----------------------------- RCVRF ----------------------------------------

SPEC = rcvrf.VRFSpec(vlen=256, elen=64, n_regs=32, n_banks=8, elem_bits=8)


def test_mapping_bijective():
    seen = set()
    for reg in range(SPEC.n_regs):
        for blk in range(SPEC.blocks_per_reg):
            loc = rcvrf.locate(SPEC, reg, blk)
            assert loc not in seen
            seen.add(loc)
    assert len(seen) == SPEC.n_regs * SPEC.blocks_per_reg


def test_paper_figure9_placement():
    # VREG0 -> Row0 Banks0..3 ; VREG4 -> Row4 Banks4..7 ; VREG8 -> Row4 Banks0..3
    assert [rcvrf.bank_of(SPEC, 0, j) for j in range(4)] == [0, 1, 2, 3]
    assert rcvrf.row_of(SPEC, 0, 0) == 0
    assert [rcvrf.bank_of(SPEC, 4, j) for j in range(4)] == [4, 5, 6, 7]
    assert rcvrf.row_of(SPEC, 4, 0) == 4
    assert rcvrf.row_of(SPEC, 8, 0) == 4
    assert [rcvrf.bank_of(SPEC, 8, j) for j in range(4)] == [0, 1, 2, 3]


@given(st.integers(0, 31))
def test_row_access_conflict_free(reg):
    banks = [rcvrf.bank_of(SPEC, reg, j) for j in range(SPEC.blocks_per_reg)]
    assert len(set(banks)) == len(banks)


@given(st.integers(0, 24), st.integers(0, 3), st.integers(1, 8))
def test_column_access_conflict_free(base, block, count):
    assert rcvrf.column_banks_distinct(SPEC, base, block, count)


@given(st.integers(0, 31))
def test_row_roundtrip(reg):
    vrf = rcvrf.empty_vrf(SPEC)
    data = (jnp.arange(32, dtype=jnp.uint8) * 5 + reg).astype(jnp.uint8)
    vrf = rcvrf.write_row(SPEC, vrf, reg, data)
    out = rcvrf.read_row(SPEC, vrf, reg)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(data))


def test_rows_do_not_clobber_each_other():
    vrf = rcvrf.empty_vrf(SPEC)
    datas = {}
    for reg in range(SPEC.n_regs):
        d = (jnp.arange(32, dtype=jnp.uint8) + 7 * reg).astype(jnp.uint8)
        vrf = rcvrf.write_row(SPEC, vrf, reg, d)
        datas[reg] = d
    for reg in range(SPEC.n_regs):
        np.testing.assert_array_equal(np.asarray(rcvrf.read_row(SPEC, vrf, reg)),
                                      np.asarray(datas[reg]))


@given(st.integers(0, 3), st.integers(0, 7), st.integers(1, 8))
def test_column_read_matches_rows(block, byte, count):
    vrf = rcvrf.empty_vrf(SPEC)
    base = 0
    rows = {}
    for i in range(count):
        d = (jnp.arange(32, dtype=jnp.uint8) * 3 + 11 * i).astype(jnp.uint8)
        vrf = rcvrf.write_row(SPEC, vrf, base + i, d)
        rows[i] = np.asarray(d)
    col = np.asarray(rcvrf.read_column(SPEC, vrf, base, block, byte, count))
    for i in range(count):
        assert col[i] == rows[i][block * SPEC.elems_per_block + byte]


@given(st.integers(0, 3), st.integers(0, 7), st.integers(1, 8))
def test_column_write_then_row_read(block, byte, count):
    """Segment-load beat: column write lands in the right register bytes."""
    vrf = rcvrf.empty_vrf(SPEC)
    vals = (jnp.arange(count, dtype=jnp.uint8) + 100).astype(jnp.uint8)
    vrf = rcvrf.write_column(SPEC, vrf, 0, block, byte, vals)
    for i in range(count):
        row = np.asarray(rcvrf.read_row(SPEC, vrf, i))
        assert row[block * SPEC.elems_per_block + byte] == 100 + i


def test_vrf_specs_other_geometries():
    for spec in [rcvrf.VRFSpec(vlen=512, elen=64, n_regs=32, n_banks=8),
                 rcvrf.VRFSpec(vlen=128, elen=32, n_regs=32, n_banks=8,
                               elem_bits=8)]:
        seen = set()
        for reg in range(spec.n_regs):
            for blk in range(spec.blocks_per_reg):
                loc = rcvrf.locate(spec, reg, blk)
                assert loc not in seen, (spec, reg, blk)
                seen.add(loc)
