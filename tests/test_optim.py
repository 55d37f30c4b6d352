"""Optimizer + compression invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.optim.adamw import (AdamWConfig, apply_updates, clip_by_global_norm,
                               init_opt_state, schedule)
from repro.optim.compression import (CompressionConfig,
                                     compress_with_feedback,
                                     init_error_state, wire_bytes_ratio)

settings.register_profile("fast4", max_examples=25, deadline=None)
settings.load_profile("fast4")


def _params():
    k = jax.random.key(0)
    return {"w": jax.random.normal(k, (8, 16)),
            "ln": jnp.ones((16,)),
            "b": jnp.zeros((16,))}


def test_schedule_warmup_and_decay():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    assert float(schedule(cfg, jnp.asarray(0))) == 0.0
    assert abs(float(schedule(cfg, jnp.asarray(10))) - 1.0) < 0.11
    assert float(schedule(cfg, jnp.asarray(100))) <= 0.1 + 1e-6
    # monotone decay after warmup
    vals = [float(schedule(cfg, jnp.asarray(s))) for s in range(10, 101, 10)]
    assert all(a >= b - 1e-9 for a, b in zip(vals, vals[1:]))


@given(st.floats(0.1, 10.0))
def test_clip_global_norm(max_norm):
    g = {"a": jnp.full((4, 4), 3.0), "b": jnp.full((2,), -4.0)}
    clipped, norm = clip_by_global_norm(g, max_norm)
    new_norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                  for x in jax.tree.leaves(clipped))))
    assert new_norm <= max_norm + 1e-4 or new_norm <= float(norm) + 1e-4


def test_adamw_reduces_quadratic_loss():
    cfg = AdamWConfig(lr=0.05, warmup_steps=0, total_steps=1000,
                      weight_decay=0.0, grad_clip=100.0)
    params = {"w": jnp.array([3.0, -2.0])}
    opt = init_opt_state(params)
    loss = lambda p: jnp.sum(jnp.square(p["w"]))
    l0 = float(loss(params))
    for _ in range(50):
        g = jax.grad(loss)(params)
        params, opt, _ = apply_updates(params, g, opt, cfg)
    assert float(loss(params)) < 0.1 * l0


def test_weight_decay_skips_norm_params():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, weight_decay=1.0,
                      grad_clip=1e9)
    params = _params()
    zeros = jax.tree.map(jnp.zeros_like, params)
    opt = init_opt_state(params)
    new, _, _ = apply_updates(params, zeros, opt, cfg)
    # ln (norm scale) untouched by decay; w decayed toward zero
    np.testing.assert_allclose(np.asarray(new["ln"]), np.asarray(params["ln"]))
    assert float(jnp.sum(jnp.abs(new["w"]))) < \
        float(jnp.sum(jnp.abs(params["w"])))


@pytest.mark.parametrize("kind", ["int8", "topk"])
def test_error_feedback_preserves_signal(kind):
    """EF invariant: sent_total + residual == true_total exactly, and the
    residual stays BOUNDED (does not grow with steps) — the property that
    makes compressed SGD convergent."""
    cfg = CompressionConfig(kind=kind, topk_frac=0.1)
    key = jax.random.key(1)
    g = {"w": jax.random.normal(key, (64,))}
    err = init_error_state(g)
    sent_total = jnp.zeros((64,))
    resids = []
    for i in range(40):
        sent, err, _ = compress_with_feedback(g, err, cfg)
        sent_total = sent_total + sent["w"]
        resids.append(float(jnp.linalg.norm(err["w"])))
    # exactness: what was not sent is exactly the residual
    np.testing.assert_allclose(np.asarray(sent_total + err["w"]),
                               np.asarray(40 * g["w"]), rtol=1e-4, atol=1e-4)
    # boundedness: residual plateaus instead of growing linearly
    assert resids[-1] < 2.0 * max(resids[:10]) + 1e-6
    assert resids[-1] < 10 * float(jnp.linalg.norm(g["w"]))


def test_compression_none_is_identity():
    g = {"w": jnp.arange(4.0)}
    sent, err, _ = compress_with_feedback(g, init_error_state(g),
                                          CompressionConfig(kind="none"))
    np.testing.assert_array_equal(np.asarray(sent["w"]), np.asarray(g["w"]))


def test_wire_ratios():
    assert wire_bytes_ratio(CompressionConfig(kind="int8")) == 0.25
    assert wire_bytes_ratio(CompressionConfig(kind="none")) == 1.0
    assert wire_bytes_ratio(CompressionConfig(kind="topk",
                                              topk_frac=0.05)) == 0.1


def test_train_step_with_compression_and_microbatches():
    from repro.configs import get_arch
    from repro.train.step import TrainConfig, init_full_state, make_train_step
    from repro.configs.base import train_batch
    arch = get_arch("qwen3-0.6b")
    cfg = arch.smoke
    tcfg = TrainConfig(
        optimizer=AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=100),
        microbatches=2,
        compression=CompressionConfig(kind="int8"))
    state = init_full_state(cfg, tcfg, jax.random.key(0))
    batch = train_batch(cfg, 32, 4, specs=False)
    step = jax.jit(make_train_step(cfg, tcfg, None))
    s1, m1 = step(state, batch)
    s2, m2 = step(s1, batch)
    assert bool(jnp.isfinite(m1["loss"])) and bool(jnp.isfinite(m2["loss"]))
    assert float(m2["loss"]) < float(m1["loss"]) + 0.5
    assert int(s2["opt"]["step"]) == 2
