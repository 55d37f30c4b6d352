"""Shared quantization helpers (repro/core/quant.py): round-trip error
bounds per dtype, zero-scale safety, the compression delegation staying
bit-exact, and the fp8 dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant


_DTYPES = ("int8", "float8_e4m3fn", "float8_e5m2")


# --------------------------- name plumbing ----------------------------------

def test_canonical_aliases_and_rejection():
    assert quant.canonical("fp8") == "float8_e4m3fn"
    assert quant.canonical("e5m2") == "float8_e5m2"
    assert quant.canonical("int8") == "int8"
    assert quant.canonical(np.dtype(np.int8)) == "int8"
    with pytest.raises(ValueError, match="unsupported quantized dtype"):
        quant.canonical("int4")
    with pytest.raises(ValueError, match="unsupported quantized dtype"):
        quant.pool_dtype("int4")
    assert quant.pool_dtype("int8") == jnp.int8


def test_qmax_values():
    assert quant.qmax("int8") == 127.0            # symmetric, not -128
    assert quant.qmax("float8_e4m3fn") == 448.0   # max finite of e4m3fn
    assert quant.qmax(jnp.int8) == 127.0          # dtype objects too
    # the bound must agree with what the dtype actually encodes
    assert float(jnp.finfo(quant.pool_dtype("fp8")).max) == 448.0


# --------------------------- round-trip bound -------------------------------

@pytest.mark.parametrize("dt", _DTYPES)
@pytest.mark.parametrize("scale", [1e-6, 1.0, 3e3])
def test_roundtrip_error_within_per_dtype_bound(dt, scale):
    """|x - roundtrip(x)| <= error_bound(dt, max|x|) for every element —
    the worst-case half-step (int8) / half-ulp (fp8) bound, at any
    tensor magnitude (the scale is max-abs, so the bound is relative)."""
    x = scale * jax.random.normal(jax.random.key(0), (512,), jnp.float32)
    y = quant.roundtrip(x, quant.pool_dtype(dt))
    bound = quant.error_bound(dt, float(jnp.max(jnp.abs(x))))
    err = float(jnp.max(jnp.abs(x - y)))
    assert np.isfinite(err)
    assert err <= bound * (1 + 1e-6), (dt, scale, err, bound)


@pytest.mark.parametrize("dt", _DTYPES)
def test_roundtrip_extremes_map_exactly(dt):
    """The max-magnitude elements sit exactly at +-qmax, which every
    quantized dtype encodes exactly — so the extremes round-trip with
    zero error and nothing saturates to inf/NaN."""
    x = jnp.asarray([-7.5, 0.0, 7.5], jnp.float32)
    y = quant.roundtrip(x, quant.pool_dtype(dt))
    np.testing.assert_allclose(np.asarray(y)[[0, 2]], [-7.5, 7.5],
                               rtol=1e-6)
    assert float(y[1]) == 0.0


@pytest.mark.parametrize("dt", _DTYPES)
def test_zero_scale_writes_zero_never_nan(dt):
    """scale == 0 means "nothing written": quantize must emit 0 (not
    0/0 = NaN — fp8 HAS NaN encodings and one NaN page poisons every
    later gather), and the all-zero tensor round-trips exactly."""
    z = jnp.zeros((8,), jnp.float32)
    q = quant.quantize(z, jnp.float32(0.0), dt)
    assert not bool(jnp.any(jnp.isnan(q.astype(jnp.float32))))
    np.testing.assert_array_equal(np.asarray(quant.roundtrip(
        z, quant.pool_dtype(dt))), np.zeros(8, np.float32))


def test_scale_for_axis_and_eps():
    x = jnp.asarray([[1.0, -4.0], [0.0, 0.0]], jnp.float32)
    s = quant.scale_for(x, "int8", axis=1)
    np.testing.assert_allclose(np.asarray(s), [4.0 / 127.0, 0.0])
    s_eps = quant.scale_for(x, "int8", axis=1, eps=1e-12)
    assert float(s_eps[1]) == pytest.approx(1e-12 / 127.0)


# --------------------------- compression delegation -------------------------

def test_compression_int8_roundtrip_delegates_bit_exact():
    """optim/compression.py's _int8_roundtrip is now quant.roundtrip —
    the delegation must be bit-exact vs the original inline formula
    (scale = max|g|/127, round, dequant) across magnitudes, or the
    error-feedback residuals drift from every pre-refactor run."""
    from repro.optim.compression import _int8_roundtrip
    for i, mag in enumerate([1e-15, 1e-3, 1.0, 1e4]):
        g = mag * jax.random.normal(jax.random.key(i), (257,), jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
        ref = jnp.round(jnp.clip(g / scale, -127, 127)).astype(
            jnp.int8).astype(jnp.float32) * scale
        np.testing.assert_array_equal(np.asarray(_int8_roundtrip(g)),
                                      np.asarray(ref))
