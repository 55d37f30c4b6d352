"""repro.vx — API contract tests.

1. Every vx verb is bit-exact with the legacy ``kernels/ops.py`` path
   across impls (``ref``, ``pallas``, ``pallas_dynamic``) and through the
   runtime-stride bank.
2. ``with vx.use(...)`` nests and restores the active policy (including
   under exceptions).
3. Plan-cache keys include dtype and vl — the int8-vs-float32 collision
   regression.
4. ``vx.Policy.default()`` is the ONE resolution point: the env var,
   ``drom.default_impl`` and ``ModelConfig.kernel_impl=None`` all agree.
5. The legacy shims still answer correctly but warn.
"""
import contextlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import vx

IMPLS = ("ref", "pallas", "pallas_dynamic")


@contextlib.contextmanager
def legacy():
    """Call deprecated shims without tripping the CI deprecation gate."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        yield


# ---------------------------------------------------------------------------
# 1. verb <-> legacy equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("stride,offset", [(1, 0), (3, 2), (8, 5)])
def test_gather_scatter_strided_match_legacy(impl, stride, offset):
    from repro.kernels import ops
    n = 128
    vl = (n - 1 - offset) // stride + 1
    win = jax.random.normal(jax.random.key(0), (3, n))
    vals = jax.random.normal(jax.random.key(1), (3, vl))
    spec = vx.Strided(n=n, stride=stride, offset=offset, vl=vl)
    with legacy():
        want_g = ops.gather_strided(win, stride, offset, vl, impl=impl)
        want_s = ops.scatter_strided(win, vals, stride, offset, impl=impl)
    np.testing.assert_array_equal(
        np.asarray(vx.gather(spec, win, policy=impl)), np.asarray(want_g))
    np.testing.assert_array_equal(
        np.asarray(vx.scatter(spec, win, vals, policy=impl)),
        np.asarray(want_s))


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("fields", [2, 4])
def test_transpose_matches_legacy(impl, fields):
    from repro.kernels import ops
    m = 32
    spec = vx.Segment(n=fields * m, fields=fields)
    aos = jax.random.normal(jax.random.key(2), (4, fields * m))
    with legacy():
        want = ops.deinterleave(aos, fields, impl=impl)
    got = vx.transpose(spec, aos, policy=impl)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    with legacy():
        want_b = ops.interleave(got, impl=impl)
    back = vx.transpose(spec, got, policy=impl)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(want_b))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(aos))


@pytest.mark.parametrize("impl", IMPLS)
def test_compact_expand_match_legacy(impl):
    from repro.kernels import ops
    n, d = 64, 16
    rows = jax.random.normal(jax.random.key(3), (n, d))
    mask = jax.random.uniform(jax.random.key(4), (n,)) < 0.4
    with legacy():
        want_p, want_v = ops.compact_rows(rows, mask, impl=impl)
    got_p, got_v = vx.compact(vx.Compact(n=n), mask, rows, policy=impl)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    with legacy():
        want_e = ops.expand_rows(got_p, mask, impl=impl)
    got_e = vx.scatter(vx.Compact(n=n), mask, got_p, policy=impl)
    np.testing.assert_array_equal(np.asarray(got_e), np.asarray(want_e))


@pytest.mark.parametrize("impl", ("ref", "pallas"))
def test_compact_cap_truncates_rows(impl):
    n, d, cap = 32, 8, 4
    rows = jax.random.normal(jax.random.key(20), (n, d))
    mask = jnp.arange(n) % 3 == 0            # 11 set bits > cap
    packed, valid = vx.compact(vx.Compact(n=n, cap=cap), mask, rows,
                               policy=impl)
    assert packed.shape == (cap, d) and valid.shape == (cap,)
    full, fv = vx.compact(vx.Compact(n=n), mask, rows, policy=impl)
    assert full.shape == (n, d)
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(full[:cap]))
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(fv[:cap]))


@pytest.mark.parametrize("impl", IMPLS)
def test_gather_many_matches_legacy(impl):
    from repro.kernels import ops
    n, vl, A = 64, 16, 3
    wins = jnp.stack([jax.random.normal(jax.random.key(5 + a), (4, n))
                      for a in range(A)])
    pairs = [(2, 0), (3, 1), (1, 5)]
    specs = [vx.Strided(n=n, stride=s, offset=o, vl=vl) for s, o in pairs]
    with legacy():
        want = ops.gather_strided_many(wins, pairs, vl, impl=impl)
    got = vx.gather_many(specs, wins, policy=impl)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", IMPLS)
def test_segment_many_match_legacy(impl):
    from repro.kernels import ops
    fields, m, A = 2, 32, 3
    spec = vx.Segment(n=fields * m, fields=fields)
    aos_list = [jax.random.normal(jax.random.key(10 + a), (4, fields * m))
                for a in range(A)]
    with legacy():
        want = ops.deinterleave_many(aos_list, fields, impl=impl)
    got = vx.gather_many(spec, aos_list, policy=impl)
    for gg, ww in zip(got, want):
        for g, w in zip(gg, ww):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    groups = got
    with legacy():
        want_b = ops.interleave_many(groups, impl=impl)
    back = vx.scatter_many(spec, groups, policy=impl)
    for g, w in zip(back, want_b):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("stride", [-3, -1, 2, 5, 11])
def test_bank_gather_matches_legacy_rt(stride):
    from repro.kernels import ops
    n, offset0, vl = 128, 0, 8
    offset = offset0 + (0 if stride > 0 else n - 1)
    win = jax.random.normal(jax.random.key(6), (2, n))
    spec = vx.Strided(n=n, stride=vx.BANK, offset=offset, vl=vl)
    with legacy():
        want = ops.gather_strided_rt(win, stride, offset, vl)
    # static stride through the BANK spec
    got = vx.gather(spec, win, stride=stride)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # traced stride through the lax.switch dispatch
    traced = jax.jit(lambda w, s: vx.gather(spec, w, stride=s))(
        win, jnp.int32(stride))
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(want))


def test_bank_scatter_traced_matches_static():
    n, vl = 64, 8
    win = jax.random.normal(jax.random.key(7), (2, n))
    vals = jax.random.normal(jax.random.key(8), (2, vl))
    spec = vx.Strided(n=n, stride=vx.BANK, offset=3, vl=vl)
    static = vx.scatter(spec, win, vals, stride=4)
    traced = jax.jit(lambda w, v, s: vx.scatter(spec, w, v, stride=s))(
        win, vals, jnp.int32(4))
    np.testing.assert_array_equal(np.asarray(traced), np.asarray(static))
    want = win.at[:, 3:3 + 4 * vl:4].set(vals)
    np.testing.assert_array_equal(np.asarray(static), np.asarray(want))


# ---------------------------------------------------------------------------
# 2. policy scoping
# ---------------------------------------------------------------------------

def test_use_nesting_restores_policy():
    base = vx.current()
    with vx.use("pallas") as outer:
        assert vx.current().impl == "pallas"
        with vx.use(impl="ref", fusion_threshold=0) as inner:
            assert vx.current() is inner
            assert inner.impl == "ref" and inner.fusion_threshold == 0
            # inner scope inherits everything else from the outer scope
            assert inner.bank_strides == outer.bank_strides
        assert vx.current() is outer
    assert vx.current() == base


def test_use_restores_on_exception():
    before = vx.current()
    with pytest.raises(RuntimeError):
        with vx.use("pallas"):
            raise RuntimeError("boom")
    assert vx.current() == before


def test_policy_arg_beats_scope():
    spec = vx.Segment(n=8, fields=2)
    aos = jnp.arange(8.0)[None]
    with vx.use("pallas"):
        # explicit arg wins over the scope
        a = vx.transpose(spec, aos, policy="ref")
        b = vx.transpose(spec, aos)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_static_spec_rejects_stride_operand():
    w = jnp.arange(64.0)[None]
    spec = vx.Strided(n=64, stride=2, vl=8)
    with pytest.raises(ValueError, match="already pins stride"):
        vx.gather(spec, w, stride=5)
    with pytest.raises(ValueError, match="stride=vx.BANK"):
        vx.gather(vx.Strided(n=64, stride=vx.BANK, vl=8), w)


def test_policy_validation():
    with pytest.raises(ValueError):
        vx.Policy(impl="mosaic")
    with pytest.raises(TypeError):
        vx.resolve(3.14)


# ---------------------------------------------------------------------------
# 3. plan-cache keys include dtype and vl (collision regression)
# ---------------------------------------------------------------------------

def _gather_prog_keys(n: int, offset: int) -> list:
    """Cached gather programs touching window width n at this offset."""
    out = []
    for k in vx.PLANS.keys():
        if not (isinstance(k, tuple) and k and k[0] == "prog"):
            continue
        for txn in k[1]:
            if txn.op == "gather.plan" and any(
                    n in sk and offset in sk for sk in txn.specs):
                out.append(txn)
    return out


def test_plan_cache_distinguishes_dtypes():
    n, stride, vl = 64, 2, 16
    w8 = jnp.arange(n, dtype=jnp.int8)[None] % 100
    w32 = jnp.arange(n, dtype=jnp.float32)[None]
    spec = vx.Strided(n=n, stride=stride, vl=vl, offset=11)
    got8 = vx.gather(spec, w8, policy="pallas")
    got32 = vx.gather(spec, w32, policy="pallas")
    assert got8.dtype == jnp.int8 and got32.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(got8), np.asarray(w8[:, 11:11 + stride * vl:stride]))
    np.testing.assert_array_equal(
        np.asarray(got32), np.asarray(w32[:, 11:11 + stride * vl:stride]))
    # the two accesses may never share a program entry: one per dtype
    txns = _gather_prog_keys(n, 11)
    dtypes = {f for t in txns for sk in t.specs for f in sk
              if f in ("int8", "float32")}
    assert {"int8", "float32"} <= dtypes, txns


def test_same_spec_two_layouts_distinct_cached_programs():
    """PR 4 regression: vx.PLANS keys include the shard layout — the same
    spec lowered against two placements yields two distinct cached
    programs (and a third for the replicated lowering)."""
    from repro.launch.mesh import make_test_mesh
    from repro.vx import lower as vxlower
    mesh_a = make_test_mesh((1,), ("a",))
    mesh_b = make_test_mesh((1,), ("b",))
    spec = vx.Strided(n=48, stride=3, vl=8, offset=1, dtype="float32")
    progs = [
        vxlower.lower("gather.plan", spec, "ref"),
        vxlower.lower("gather.plan", spec, "ref",
                      vx.Shard(axes=("a",), axis=-1, mesh=mesh_a)),
        vxlower.lower("gather.plan", spec, "ref",
                      vx.Shard(axes=("b",), axis=-1, mesh=mesh_b)),
    ]
    keys = {p.key() for p in progs}
    assert len(keys) == 3, keys
    # executing all three populates three distinct cache entries, and the
    # 1-shard shard_map lowerings agree with the replicated one
    w = jnp.arange(48, dtype=jnp.float32)[None]
    shards = [None,
              vx.Shard(axes=("a",), axis=-1, mesh=mesh_a),
              vx.Shard(axes=("b",), axis=-1, mesh=mesh_b)]
    outs = [vxlower.executor(p, spec, sh)(w)
            for p, sh in zip(progs, shards)]
    for o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(o), np.asarray(outs[0]))
    cached = [p.key() for p in progs if p.key() in vx.PLANS]
    assert len(cached) == 3, cached


def test_layout_key_includes_mesh():
    """The mesh is part of the layout key: the compiled sharded executor
    closes over its mesh (shard_map + shard-index flattening), so two
    unequal meshes — even with the same axis names and shard count —
    must not share an entry (e.g. a (2,4) and a (4,2) mesh over the same
    axes)."""
    from repro.launch.mesh import make_test_mesh
    from repro.vx import lower as vxlower
    mesh_ab = make_test_mesh((1, 1), ("a", "b"))
    mesh_ba = make_test_mesh((1, 1), ("b", "a"))   # unequal mesh, same names
    spec = vx.Strided(n=48, stride=2, vl=8, dtype="float32")
    p1 = vxlower.lower("gather.plan", spec, "ref",
                       vx.Shard(axes=("a", "b"), axis=-1, mesh=mesh_ab))
    p2 = vxlower.lower("gather.plan", spec, "ref",
                       vx.Shard(axes=("a", "b"), axis=-1, mesh=mesh_ba))
    assert p1.key() != p2.key()
    assert p1.key() == vxlower.lower(
        "gather.plan", spec, "ref",
        vx.Shard(axes=("a", "b"), axis=-1, mesh=mesh_ab)).key()


def test_sharded_gather_many_rejects_heterogeneous_specs():
    """program.fuse reaches the sharded builder with width > 1; a
    heterogeneous group must error, never apply spec 0's plan to every
    stacked row."""
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((1,), ("a",))
    shard = vx.Shard(axes=("a",), axis=-1, mesh=mesh)
    wins = jnp.stack([jnp.arange(64.0)] * 2)[:, None, :]
    specs = [vx.Strided(n=64, stride=2, offset=0, vl=8),
             vx.Strided(n=64, stride=3, offset=1, vl=8)]
    with pytest.raises(NotImplementedError, match="heterogeneous"):
        vx.gather_many(specs, wins, policy="ref", shard=shard)
    # homogeneous fused groups keep their sharded lowering
    same = [vx.Strided(n=64, stride=2, offset=0, vl=8)] * 2
    got = vx.gather_many(same, wins, policy="ref", shard=shard)
    want = vx.gather_many(same, wins, policy="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sharded_lowering_rejects_bad_placements():
    from repro.launch.mesh import make_test_mesh
    from repro.vx import lower as vxlower
    mesh = make_test_mesh((1,), ("a",))
    sh_lane = vx.Shard(axes=("a",), axis=-1, mesh=mesh)
    sh_outer = vx.Shard(axes=("a",), axis=-2, mesh=mesh)
    with pytest.raises(ValueError, match="lane axis"):
        vxlower.lower("gather.plan", vx.Strided(n=8, stride=2, vl=4),
                      "ref", sh_outer)
    with pytest.raises(ValueError, match="permutes the lane axis"):
        vxlower.lower("seg.deint", vx.Segment(n=8, fields=2), "ref",
                      sh_lane)
    with pytest.raises(NotImplementedError):
        vxlower.lower("compact.rows", vx.Compact(n=8), "ref", sh_lane)
    with pytest.raises(NotImplementedError, match="runtime-stride"):
        vxlower.lower("gather.plan", vx.Strided(n=8, stride=vx.BANK, vl=4),
                      "ref", sh_lane)
    with pytest.raises(ValueError, match="counts from the end"):
        vx.Shard(axes=("a",), axis=1, mesh=mesh)


def test_verbs_lower_through_programs():
    """The pipeline is the ONE path: a verb call lands a 'prog'-keyed
    entry whose transaction carries the spec key (dtype + vl included)."""
    spec = vx.Strided(n=40, stride=5, vl=8, offset=2)
    w = jnp.arange(40, dtype=jnp.float16)[None]
    vx.gather(spec, w, policy="ref")
    bound = spec.bind(w.dtype)
    want = vx.program.single("gather.plan", bound, "ref")
    assert want.key() in vx.PLANS


def test_plan_cache_distinguishes_vl():
    n = 64
    w = jnp.arange(n, dtype=jnp.float32)[None]
    a = vx.gather(vx.Strided(n=n, stride=2, vl=8, offset=0), w)
    b = vx.gather(vx.Strided(n=n, stride=2, vl=16, offset=0), w)
    assert a.shape == (1, 8) and b.shape == (1, 16)
    assert vx.Strided(n=n, stride=2, vl=8).key() != \
        vx.Strided(n=n, stride=2, vl=16).key()


def test_spec_hashable_and_frozen():
    s = vx.Strided(n=32, stride=4, vl=8, dtype=jnp.float32)
    assert s == vx.Strided(n=32, stride=4, vl=8, dtype="float32")
    assert hash(s) == hash(vx.Strided(n=32, stride=4, vl=8, dtype="float32"))
    with pytest.raises(Exception):
        s.n = 64  # frozen
    assert {s: 1}[s] == 1
    b = vx.Strided(n=32, stride=vx.BANK, vl=8)
    assert b.runtime and "bank" in b.key()
    with pytest.raises(ValueError):
        vx.Strided(n=32, stride=8, vl=8)      # leaves the window
    with pytest.raises(ValueError):
        vx.Segment(n=33, fields=2)            # not divisible
    p = vx.Paged(page_size=8, pages=4, trail=2, dtype=jnp.float32)
    assert p == vx.Paged(page_size=8, pages=4, trail=2, dtype="float32")
    assert p.seq_len == 32 and p.pool_axis(5) == 1
    assert {p: 2}[p] == 2
    with pytest.raises(ValueError):
        vx.Paged(page_size=0, pages=4)
    i = vx.Indexed(n=4, routing=((0, 1, 1, 2), (1, 1, 0, 1)))
    assert i.static and i.key() != vx.Indexed(n=4).key()
    with pytest.raises(ValueError):
        vx.Indexed(n=4, routing=((0, 1), (1, 1)))   # wrong arity


def test_paged_verbs_validate_operands():
    pool = jnp.zeros((4, 4, 2), jnp.float32)
    spec = vx.Paged(page_size=4, pages=2, trail=1)
    with pytest.raises(ValueError, match="table="):
        vx.gather(spec, pool)
    with pytest.raises(ValueError, match="table= and pos="):
        vx.scatter(spec, pool, jnp.zeros((1, 2)))
    with pytest.raises(ValueError, match="page_size"):
        vx.gather(vx.Paged(page_size=8, pages=2, trail=1), pool,
                  table=jnp.zeros((1, 2), jnp.int32))
    with pytest.raises(ValueError, match="shift=/valid="):
        vx.gather(vx.Indexed(n=4, routing=((0, 0, 0, 0), (1, 1, 1, 1))),
                  jnp.zeros((4,)), shift=np.zeros(4, np.int32),
                  valid=np.ones(4, bool))


# ---------------------------------------------------------------------------
# 4. one knob: env var -> Policy.default -> drom/default + ModelConfig
# ---------------------------------------------------------------------------

def test_default_policy_resolves_env(monkeypatch):
    monkeypatch.setenv(vx.policy.ENV_VAR, "pallas")
    assert vx.Policy.default().impl == "pallas"
    from repro.core import drom
    with legacy():
        assert drom.default_impl() == "pallas"
    from repro.models.transformer import ModelConfig
    cfg = ModelConfig(name="t", d_model=8, n_layers=1, n_heads=1,
                      n_kv_heads=1, d_ff=16, vocab=11)
    assert cfg.kernel_impl is None
    assert cfg.vx_policy.impl == "pallas"
    monkeypatch.delenv(vx.policy.ENV_VAR)
    assert cfg.vx_policy.impl == vx.Policy.default().impl
    # a pinned impl string still wins
    import dataclasses
    pinned = dataclasses.replace(cfg, kernel_impl="ref")
    assert pinned.vx_policy.impl == "ref"


def test_warm_resolves_policy_like_verbs():
    """vx.warm honors policy= / the vx.use scope / the env default exactly
    like the verbs, so prewarming compiles the plans the governing policy
    will actually hit — and nothing under impl='ref', whose XLA path never
    consults segment plans."""
    n = 192                      # distinctive width: nothing else warms it
    key = ("plan.segment_deint", n, 2)
    assert key not in vx.PLANS
    with vx.use("ref"):
        vx.warm(n, strided=False, fields=(2,))
    assert key not in vx.PLANS
    with vx.use("pallas"):
        vx.warm(n, strided=False, fields=(2,))
    assert key in vx.PLANS
    # explicit policy= beats the scope, like any verb
    n2 = 224
    with vx.use("ref"):
        vx.warm(n2, strided=False, fields=(2,), policy="pallas")
    assert ("plan.segment_deint", n2, 2) in vx.PLANS


# ---------------------------------------------------------------------------
# 5. shims warn (and only the shims)
# ---------------------------------------------------------------------------

def test_shims_emit_deprecation_warnings():
    from repro.core import drom
    from repro.kernels import ops
    aos = jnp.arange(8.0)[None]
    with pytest.warns(DeprecationWarning):
        ops.deinterleave(aos, 2)
    with pytest.warns(DeprecationWarning):
        drom.deinterleave(aos, 2)
    with pytest.warns(DeprecationWarning):
        drom.default_impl()


def test_vx_verbs_do_not_warn():
    spec = vx.Segment(n=8, fields=2)
    aos = jnp.arange(8.0)[None]
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        vx.transpose(spec, aos)
        vx.gather(vx.Strided(n=8, stride=2, vl=4), aos)
        vx.compact(vx.Compact(n=8), jnp.ones(8, bool),
                   jnp.ones((8, 4)))
