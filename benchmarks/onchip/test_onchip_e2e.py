"""End-to-end arithmetic and the per-layer readers on hand-made logs."""
import json
from pathlib import Path

import numpy as np
import pytest

import e2e_metrics as e2e
import run_cell
import workcount
from client import Log, ReqLog, TickLog

HERE = Path(__file__).resolve().parent


def req(idx, due, toks, *, served=None):
    r = ReqLog(idx, due, np.zeros(4, np.int32), max_new=len(toks) or 1)
    r.tok_t, r.tokens = list(toks), served
    return r


def hand_log():
    """Window 100..110 s; four requests due in it, one after it."""
    reqs = [
        req(0, 100.0, [101.0, 102.0, 103.0, 104.0], served=[1, 2, 3, 4]),
        req(1, 101.0, [105.0, 107.0]),
        # a token after the close is not delivered in the window
        req(2, 102.0, [109.0, 111.0]),
        # due in the window, never served: counts at its wait so far
        req(3, 106.0, []),
        req(4, 111.0, [112.0]),
    ]
    ticks = [TickLog(100.0 + i, 101.0 + i, prefill_tokens=16,
                     decode_tokens=2, attn_pairs=300, kv_ctx_tokens=40,
                     pages_in_use=10 + i) for i in range(10)]
    return Log(100.0, 110.0, reqs, ticks, num_pages=40)


def test_tokens_per_s_counts_tokens_delivered_in_the_window():
    assert e2e.tokens_per_s(hand_log()) == pytest.approx(7 / 10)


def test_ttft_counts_unserved_requests_at_their_wait_so_far():
    assert e2e.ttfts(hand_log()) == pytest.approx([1.0, 4.0, 7.0, 4.0])
    assert e2e.percentile(e2e.ttfts(hand_log()), 90) == pytest.approx(
        np.percentile([1.0, 4.0, 7.0, 4.0], 90))
    assert e2e.end_to_end(hand_log(), setup_s=12.5)["setup_s"] == (12.5, "s")


def test_tpot_is_a_tail_over_requests_with_two_tokens_in_the_window():
    # request 0: (104 - 101) / 3; request 1: (107 - 105) / 1; request 2
    # has one token in the window
    assert e2e.tpots(hand_log()) == pytest.approx([1.0, 2.0])
    assert e2e.end_to_end(hand_log(), 0.0)["tpot_p90_ms"][0] == \
        pytest.approx(1e3 * np.percentile([1.0, 2.0], 90))


def view(cell, log, trace=None):
    c = json.loads((HERE / "configs" / "qwen3-0.6b.json").read_text())
    return run_cell.View(cell, c, {}, log, trace,
                         {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})


def test_per_layer_readers_on_a_hand_made_log():
    v = view("x", hand_log())
    assert run_cell.read_metric("tick_ms.offline", v) == pytest.approx(1e3)
    assert run_cell.read_metric("pool_used_share.offline", v) == \
        pytest.approx(100 * 14.5 / 40)
    c = v.config
    flops = workcount.model_flops(c, v.log.ticks)
    assert run_cell.read_metric("mfu.offline", v) == pytest.approx(
        100 * flops / (10 * 197e12))
    assert run_cell.read_metric("hbm_share.offline", v) == pytest.approx(
        100 * workcount.least_bytes(c, v.log.ticks) / (10 * 819e9))
    assert run_cell.read_metric("device_idle.offline", v) is None


def test_work_counts_from_shapes():
    c = json.loads((HERE / "configs" / "qwen3-0.6b.json").read_text())
    # qwen3-0.6b: 28 layers of q (1024x2048), k and v (1024x1024 each),
    # o (2048x1024) and a gated MLP (3 x 1024x3072)
    assert workcount.layer_matmul_params(c) == \
        1024 * 2048 * 2 + 2 * 1024 * 1024 + 3 * 1024 * 3072
    t = TickLog(0, 1, prefill_tokens=3, decode_tokens=1, attn_pairs=10,
                kv_ctx_tokens=5)
    assert workcount.model_flops(c, [t]) == (
        2 * 28 * workcount.layer_matmul_params(c) * 4
        + 4 * 28 * 16 * 128 * 10 + 2 * 151936 * 1024)
    assert workcount.kv_bytes_per_token(c) == 229376     # float32 pool
    assert workcount.least_bytes(c, [t, TickLog(1, 2)]) == \
        workcount.weight_bytes(c) + 229376 * (5 + 4)
