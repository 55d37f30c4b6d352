"""Operations and bytes the served work needs, from shapes alone.

Nothing here asks the program what it did: the counts follow from the
configuration, the serving geometry and the tokens the client saw the
program process (``client.TickLog``).  So an implementation that fuses,
skips or casts once is measured against the same work.
"""
from __future__ import annotations

import numpy as np

from references import dense_decoder as ref


def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies by in one layer."""
    m = ref.dims(c)
    d, H, K, D, F = m["d"], m["H"], m["K"], m["D"], m["F"]
    return d * H * D + 2 * d * K * D + H * D * d + 3 * d * F


def weight_bytes(c: dict) -> int:
    """Every weight once, at the compute dtype's width."""
    m = ref.dims(c)
    width = np.dtype(c["program"]["compute_dtype"]).itemsize
    norms = 2 * m["d"] + (2 * m["D"] if m["qk_norm"] else 0)
    head = 0 if m["tied"] else m["V"] * m["d"]
    return width * (m["L"] * (layer_matmul_params(c) + norms)
                    + m["V"] * m["d"] + head + m["d"])


def kv_bytes_per_token(c: dict) -> int:
    """Pool bytes of one token across all layers (K and V)."""
    m = ref.dims(c)
    width = np.dtype(c["serving"]["kv_dtype"]).itemsize
    return m["L"] * m["K"] * 2 * m["D"] * width


def model_flops(c: dict, ticks) -> float:
    """2 x matmul weights per processed token, plus attention (QK and PV)
    over each token's live context, plus the head for every token that
    yields logits (decode positions; prefill chunks yield none)."""
    m = ref.dims(c)
    toks = sum(t.prefill_tokens + t.decode_tokens for t in ticks)
    pairs = sum(t.attn_pairs for t in ticks)
    dec = sum(t.decode_tokens for t in ticks)
    return (2.0 * m["L"] * layer_matmul_params(c) * toks
            + 4.0 * m["L"] * m["H"] * m["D"] * pairs
            + 2.0 * m["V"] * m["d"] * dec)


def least_bytes(c: dict, ticks) -> float:
    """Per tick with work: the weights once, each sequence's live KV read
    once, and the KV it writes."""
    w, kv = weight_bytes(c), kv_bytes_per_token(c)
    total = 0.0
    for t in ticks:
        n = t.prefill_tokens + t.decode_tokens
        if n:
            total += w + kv * (t.kv_ctx_tokens + n)
    return total
