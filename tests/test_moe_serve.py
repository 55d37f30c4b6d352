"""The expert layer that holds a share of the experts, on the serve path,
against a plain reference at smoke width on the CPU: the shares of a
layer add up to the uncut layer, routing skewed onto the held experts
drops nothing, masked tokens route nowhere, a held-range model served
through ``Scheduler`` (chunked prefill, then paged decode with idle
slots) gives the reference's logits, and the layer's counters total the
units routed here without a host read per tick.

The reference (``ref_moe``, ``ref_logits``) is plain float32
``jax.numpy`` over the program's parameter layout: per token a softmax
over every expert's router logit, the top k renormalised over those k,
and the weighted SwiGLU outputs of the chosen experts that the share
holds, computed densely with no capacity and no batching."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.moe import MoESpec, init_moe, moe_ffn_local, moe_layer
from repro.models.transformer import init_params
from repro.serve.paged_cache import PagedCache
from repro.serve.scheduler import Scheduler

D, T = 64, 24
UNCUT = MoESpec(n_experts=16, top_k=4, d_ff=64)
SHARES = [UNCUT._replace(held=4, first_expert=4 * r) for r in range(4)]
HIGHEST = jax.lax.Precision.HIGHEST


def ref_moe(p, h, spec: MoESpec):
    probs = jax.nn.softmax(jnp.matmul(h, p["router"], precision=HIGHEST), -1)
    topw, topi = jax.lax.top_k(probs, spec.top_k)
    topw = topw / jnp.sum(topw, -1, keepdims=True)
    held = spec.first_expert + jnp.arange(spec.n_held)
    gate = jnp.sum(jnp.where(topi[:, :, None] == held, topw[:, :, None],
                             0.0), 1)                               # (T, e)
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    a = (jax.nn.silu(ein("td,edf->etf", h, p["wg"]))
         * ein("td,edf->etf", h, p["wu"]))
    return ein("te,etd->td", gate, ein("etf,efd->etd", a, p["wo"]))


def _rms(x, g, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    S, _, Dh = x.shape
    half = Dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def ref_logits(params, cfg, tokens):
    """The full forward pass over ``tokens``: logits (S, vocab)."""
    S, H, K, Dh = len(tokens), cfg.n_heads, cfg.n_kv_heads, cfg.hd
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    x = params["embed"][jnp.asarray(tokens)]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[layer], params["blocks"]["pos0"])
        h = _rms(x, p["ln1"], cfg.norm_eps)
        q = _rms(mm(h, p["attn"]["wq"]).reshape(S, H, Dh), p["attn"]["q_norm"])
        kv = mm(h, p["attn"]["wkv"]).reshape(S, K, Dh, 2)   # K|V per feature
        k, v = _rms(kv[..., 0], p["attn"]["k_norm"]), kv[..., 1]
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
        k, v = jnp.repeat(k, H // K, 1), jnp.repeat(v, H // K, 1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * Dh ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST)
        x = x + mm(o.reshape(S, H * Dh), p["attn"]["wo"])
        x = x + ref_moe(p["moe"], _rms(x, p["ln2"], cfg.norm_eps), cfg.moe)
    return mm(_rms(x, params["final_norm"], cfg.norm_eps),
              params["lm_head"].T)


def moe_serve(params, x, spec, valid):
    """The layer as the serve path runs it: one device, masked tokens."""
    y, _, units = moe_ffn_local(
        params["router"], params["wg"], params["wu"], params["wo"], x, spec,
        model_axis=None, data_axes=(), n_shards=1, valid=valid)
    return y, units


def _share(params, spec):
    lo = spec.first_expert
    return dict(params, **{n: params[n][lo:lo + spec.n_held]
                           for n in ("wg", "wu", "wo")})


def _x(seed=1):
    return jax.random.normal(jax.random.key(seed), (T, D))


@pytest.mark.parametrize("dispatch", ["earth", "sort"])
def test_the_shares_add_up_to_the_uncut_layer(dispatch):
    """The four disjoint held ranges' outputs sum to the layer that holds
    all 16 experts, which is the training path's unsharded layer too; each
    share gives the reference's share.  Tolerances: float32 throughout,
    only the order of the per-token sums over experts differs."""
    uncut = UNCUT._replace(dispatch=dispatch)
    params = init_moe(jax.random.key(0), D, uncut, jnp.float32)
    x, valid = _x(), jnp.ones((T,), bool)
    whole, units = moe_serve(params, x, uncut, valid)
    np.testing.assert_allclose(whole, moe_layer(params, x[None], uncut,
                                                None)[0][0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(whole, ref_moe(params, x, uncut), rtol=1e-5,
                               atol=1e-6)
    parts, routed = [], 0
    for spec in SHARES:
        spec = spec._replace(dispatch=dispatch)
        share = _share(params, spec)
        y, u = moe_serve(share, x, spec, valid)
        np.testing.assert_allclose(y, ref_moe(share, x, spec), rtol=1e-5,
                                   atol=1e-6)
        parts.append(y)
        routed += int(u["routed"])
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-6)
    assert routed == int(units["routed"]) == T * uncut.top_k


def _skewed_share(spec):
    """A share and an input whose every routed unit lands on the held
    experts: a dominant positive first feature that only the held
    experts' router columns read positively."""
    share = _share(init_moe(jax.random.key(0), D, UNCUT, jnp.float32), spec)
    held = jnp.arange(UNCUT.n_experts) - spec.first_expert
    col = jnp.where((held >= 0) & (held < spec.n_held), 1.0, -1.0)
    share["router"] = share["router"].at[0].set(8.0 * col)
    return share, _x().at[:, 0].set(4.0)


def test_routing_skewed_onto_the_held_experts_drops_nothing():
    """Every token's top 4 are this share's 4 experts: the serve path's
    buffer holds all T * 4 units and gives the reference's output, where
    the mesh-derived training capacity for a quarter of the experts
    (4 shards, slack 2) holds half of them."""
    spec = SHARES[2]
    share, x = _skewed_share(spec)
    valid = jnp.ones((T,), bool)
    y, units = moe_serve(share, x, spec, valid)
    assert int(units["routed"]) == T * spec.top_k
    assert int(units["dropped"]) == 0
    assert units["per_expert"].tolist() == [T] * spec.n_held
    np.testing.assert_allclose(y, ref_moe(share, x, spec), rtol=1e-5,
                               atol=1e-6)
    _, _, trained = moe_ffn_local(
        share["router"], share["wg"], share["wu"], share["wo"], x, spec,
        model_axis=None, data_axes=(), n_shards=4)
    assert int(trained["dropped"]) == T * spec.top_k // 2


def test_masked_tokens_route_nowhere():
    """Masked tokens take no units and get no expert output; the others
    get what they get alone."""
    spec = SHARES[2]
    share, x = _skewed_share(spec)
    valid = jnp.arange(T) % 3 != 0
    y, units = moe_serve(share, x, spec, valid)
    n = int(valid.sum())
    assert int(units["routed"]) == n * spec.top_k
    assert units["per_expert"].tolist() == [n] * spec.n_held
    np.testing.assert_array_equal(np.asarray(y)[~np.asarray(valid)], 0.0)
    alone, _ = moe_serve(share, x[valid], spec, jnp.ones((n,), bool))
    np.testing.assert_allclose(np.asarray(y)[np.asarray(valid)], alone,
                               rtol=1e-6, atol=1e-7)


@functools.lru_cache(maxsize=None)
def _model():
    cfg = get_arch("qwen3-moe-30b-a3b").smoke
    return cfg, init_params(cfg, jax.random.key(0))


def test_the_smoke_config_holds_a_share():
    cfg = _model()[0]
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.n_held,
            cfg.moe.first_expert) == (16, 4, 4, 0)
    moe = _model()[1]["blocks"]["pos0"]["moe"]
    assert moe["router"].shape == (cfg.n_layers, cfg.d_model, 16)
    assert moe["wg"].shape == (cfg.n_layers, 4, cfg.d_model, cfg.moe.d_ff)


def test_served_logits_match_the_reference_forward_pass():
    """Two requests over three slots, one 16-token chunk a tick: the
    9-token prompt decodes while the 21-token one is mid-prefill, slot 2
    is idle throughout and slot 1 once its request ends.  Every decode
    step's logits match the reference's full forward pass over the prompt
    and the served tokens.  Tolerance: float32 compute on both sides (the
    smoke config's), differing only in summation order (chunked attention
    over pages, grouped expert products), well inside a bfloat16 step."""
    cfg, params = _model()
    s = Scheduler(cfg, params, slots=3, max_len=64, page_size=16)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, 21).tolist(),
               rng.integers(1, cfg.vocab, 9).tolist()]
    reqs = [s.submit(p, max_new_tokens=n) for p, n in zip(prompts, (5, 3))]
    seen = {0: [], 1: [], 2: []}
    step = s._step

    def spy(p, c, t, a):
        logits, c = step(p, c, t, a)
        for slot in np.flatnonzero(np.asarray(a)):
            seen[int(slot)].append(np.asarray(logits[slot]))
        return logits, c
    s._step = spy
    s.tick()
    slots = [r.slot for r in reqs]
    assert slots == [0, 1] and len(seen[1]) == 1 and not seen[0]
    while not s.drained():
        s.tick()
    assert not seen[2]
    for r, slot in zip(reqs, slots):
        P = len(r.prompt)
        got = np.stack(seen[slot])
        assert len(got) == r.max_new_tokens
        want = np.asarray(ref_logits(params, cfg, r.tokens[:-1]))[P - 1:]
        assert np.max(np.abs(got - want)) < 1e-4 * max(
            1.0, np.abs(want).max())


def _skewed_model():
    """The smoke model with every routed unit on the held experts: a
    dominant first feature of the embedding that no attention or expert
    output writes to, read positively by the held experts' router columns
    only."""
    cfg, params = _model()
    b = params["blocks"]["pos0"]
    held = jnp.arange(cfg.moe.n_experts) - cfg.moe.first_expert
    col = jnp.where((held >= 0) & (held < cfg.moe.n_held), 1.0, -1.0)
    moe = dict(b["moe"], router=jnp.zeros_like(b["moe"]["router"])
               .at[:, 0].set(col), wo=b["moe"]["wo"].at[..., 0].set(0.0))
    attn = dict(b["attn"], wo=b["attn"]["wo"].at[..., 0].set(0.0))
    blocks = {"pos0": dict(b, moe=moe, attn=attn)}
    return cfg, dict(params, embed=params["embed"].at[:, 0].set(8.0),
                     blocks=blocks)


def test_moe_counters_total_the_units_routed_here_without_a_host_read(
        monkeypatch):
    """A 20-token prompt (two chunks at one a tick), then a 9-token one:
    every token a chunk or a step processes sends all 4 of its units to
    the 4 held experts in both layers, pad tokens and idle slots none.
    The ticks make the reads the dense model's do (``test_serve_trace``):
    the counters stay on the device until ``stats()``."""
    cfg, params = _skewed_model()
    s = Scheduler(cfg, params, slots=3, max_len=64, page_size=16)
    reqs = [s.submit(list(range(1, 21)), max_new_tokens=3)]
    reads = [0]
    free_pages, asarray = PagedCache.free_pages, np.asarray

    def counted_free(cache):
        reads[0] += 1
        return free_pages(cache)

    def counted_asarray(a, *args, **kw):
        reads[0] += isinstance(a, jax.Array)
        return asarray(a, *args, **kw)
    monkeypatch.setattr(PagedCache, "free_pages", counted_free)
    monkeypatch.setattr(np, "asarray", counted_asarray)
    counts, witnessed = [], []
    for _ in range(2):
        n, r = s.host_syncs, reads[0]
        s.tick()
        counts.append(s.host_syncs - n)
        witnessed.append(reads[0] - r)
    assert counts == [4, 5] == witnessed
    monkeypatch.undo()
    reqs.append(s.submit(list(range(30, 39)), max_new_tokens=2))
    while not s.drained():
        s.tick()
    tokens = sum(len(r.prompt) - 1 + r.max_new_tokens for r in reqs)
    per_expert = tokens * cfg.n_layers
    assert s.stats()["moe"] == {
        "routed": per_expert * cfg.moe.top_k, "dropped": 0,
        "per_expert": [per_expert] * cfg.moe.n_held}


def test_verify_step_counts_the_units_of_its_real_columns():
    """Speculative verify runs the expert layer with its mask: columns
    past a slot's ``n_draft`` and idle slots route nowhere, the real
    columns send all 4 units to the held experts in both layers."""
    from repro.models.decode import init_paged_cache, paged_verify_step
    cfg, params = _skewed_model()
    cache = init_paged_cache(cfg, 3, 64, 16, jnp.float32)
    tokens = jnp.arange(1, 13, dtype=jnp.int32).reshape(3, 4)
    *_, out = paged_verify_step(params, cache, tokens, cfg, None,
                                n_draft=jnp.array([4, 2, 3]),
                                active=jnp.array([True, True, False]))
    per_expert = (4 + 2) * cfg.n_layers
    assert jax.tree.map(lambda a: a.tolist(), out["moe"]) == {
        "routed": per_expert * cfg.moe.top_k, "dropped": 0,
        "per_expert": [per_expert] * cfg.moe.n_held}


def test_stats_folds_the_counters_into_host_integers():
    """``stats()`` adds the device counters to host integers and zeroes
    them, so a second read gives the same totals and later work adds to
    them."""
    cfg, params = _skewed_model()
    s = Scheduler(cfg, params, slots=3, max_len=64, page_size=16)

    def serve():
        s.submit(list(range(1, 10)), max_new_tokens=2)
        while not s.drained():
            s.tick()
    serve()
    first = s.stats()["moe"]
    assert first["routed"] == (8 + 2) * cfg.n_layers * cfg.moe.top_k
    assert all(int(np.sum(a)) == 0
               for a in jax.tree.leaves(s.cache.state["moe"]))
    assert s.stats()["moe"] == first
    serve()
    assert s.stats()["moe"] == {
        "routed": 2 * first["routed"], "dropped": 0,
        "per_expert": [2 * n for n in first["per_expert"]]}
