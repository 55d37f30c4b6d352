"""Shared symmetric quantization helpers.

One module, two consumers:

* ``optim/compression.py`` — per-TENSOR int8 round-trip for gradient
  compression (error feedback keeps the bias bounded),
* the quantized paged KV pool (``vx/lower.py`` + ``models/decode.py``) —
  per-PAGE-per-head scales stored in a side tensor, dequant fused into
  the page-gather program, quantize-on-write in the append/prefill
  scatter.

All quantization here is symmetric (no zero point): ``q = x / scale``
clipped to ``[-qmax, qmax]`` and rounded for integer targets, ``x' =
q * scale``.  A scale of exactly 0 means "nothing written yet" — the
safe-divide in :func:`quantize` writes 0 (never NaN — fp8 HAS NaN
encodings and a NaN page poisons every later gather), and dequant
multiplies garbage ints by 0.

fp8 is ``float8_e4m3fn`` (``e5m2`` also accepted).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# Largest representable magnitude per quantized dtype.  int8 uses the
# symmetric range [-127, 127] (not -128: symmetry keeps dequant
# unbiased).  float8_e4m3fn's max finite is 448 (the "fn" variant trades
# inf for range); e5m2 listed for completeness.
_QMAX = {
    "int8": 127.0,
    "float8_e4m3fn": 448.0,
    "float8_e5m2": 57344.0,
}

# Worst-case round-to-nearest error of a value at magnitude ``qmax *
# scale`` quantized into the dtype, as a fraction of that magnitude:
#   int8            : half a step  => (1/127) / 2
#   float8_e4m3fn   : 3 mantissa bits => half-ulp relative 2**-4
#   float8_e5m2     : 2 mantissa bits => half-ulp relative 2**-3
_REL_ERR = {
    "int8": 0.5 / 127.0,
    "float8_e4m3fn": 2.0 ** -4,
    "float8_e5m2": 2.0 ** -3,
}

_ALIASES = {"fp8": "float8_e4m3fn", "e4m3": "float8_e4m3fn",
            "e5m2": "float8_e5m2"}


def canonical(name) -> str:
    """Canonical dtype string for a user-facing name or dtype object."""
    s = str(name)
    s = _ALIASES.get(s, s)
    if s not in _QMAX:
        raise ValueError(f"unsupported quantized dtype {name!r}; "
                         f"known: {sorted(_QMAX) + sorted(_ALIASES)}")
    return s


def pool_dtype(name):
    """jnp dtype object for a canonical/user-facing quantized dtype name."""
    return getattr(jnp, canonical(name))


def qmax(dtype) -> float:
    """Largest encodable magnitude of a quantized dtype."""
    return _QMAX[canonical(np.dtype(dtype).name
                           if not isinstance(dtype, str) else dtype)]


def scale_for(x, dtype, *, axis=None, keepdims: bool = False,
              eps: float = 0.0):
    """Symmetric max-abs scale so that |x| maps into [-qmax, qmax]."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=keepdims)
    if eps:
        amax = jnp.maximum(amax, eps)
    return amax / qmax(dtype)


def quantize(x, scale, dtype):
    """``round(clip(x / scale))`` cast to ``dtype``; scale==0 writes 0."""
    qd = pool_dtype(dtype) if isinstance(dtype, str) else dtype
    safe = jnp.where(scale > 0, scale, 1.0)
    y = jnp.where(scale > 0, x / safe, 0.0)
    return requantize(y, qd)


def requantize(y, dtype):
    """Clip+round a value already in the quantized domain and cast."""
    qd = pool_dtype(dtype) if isinstance(dtype, str) else dtype
    qm = qmax(qd)
    y = jnp.clip(y, -qm, qm)
    if jnp.issubdtype(jnp.dtype(qd), jnp.integer):
        y = jnp.round(y)
    return y.astype(qd)


def dequantize(q, scale, dtype=jnp.float32):
    return q.astype(dtype) * scale.astype(dtype)


def roundtrip(x, dtype=jnp.int8, *, eps: float = 0.0):
    """Per-tensor symmetric quantize->dequantize (compression wire sim)."""
    s = scale_for(x, dtype, eps=eps)
    return dequantize(quantize(x, s, dtype), s, jnp.float32)


def error_bound(dtype, amax):
    """Worst-case |x - roundtrip(x)| for |x| <= amax under a per-tensor
    max-abs scale.  int8: half a quantization step.  fp8: half-ulp
    relative error at the top binade dominates the subnormal floor."""
    return float(amax) * _REL_ERR[canonical(
        np.dtype(dtype).name if not isinstance(dtype, str) else dtype)]
