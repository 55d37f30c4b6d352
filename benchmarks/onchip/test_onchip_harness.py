"""The harness on the CPU at smoke geometry: a whole run past the chip
check, runs with the timed path broken underneath (``correct`` must come
out false), the command's refusal of a host without a TPU, and the
reference against the program's prefill plus paged decode."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run_cell
from adapters import dense_decoder as adapter
from references import dense_decoder as ref

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SMOKE = dict(hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, intermediate_size=256,
             vocab_size=512)
SERVING = dict(slots=4, max_len=128, page_size=16, kv_dtype="float32",
               chunk_pages=2)


def smoke_config(name, **over):
    c = json.loads((HERE / "configs" / f"{name}.json").read_text())
    c.update(SMOKE, serving=dict(SERVING), **over)
    return c


def smoke_mix():
    m = json.loads((HERE / "traffic" / "chat-offline.json").read_text())
    m["arrival"]["requests"] = 12
    m["prompt_len"].update(median=24, min=8, max=64)
    m["output_len"].update(median=8, min=4, max=16)
    m["check_tokens"] = 32
    return m


def run_smoke(c=None, *, seconds=2.0):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, _, _, _, e2e, per_layer = run_cell.cell_spec(
        bench, "qwen3-0.6b.chat-offline")
    limits = {"gap_max": 0.05, "min_tokens": 1}
    return run_cell.run(cell, c or smoke_config("qwen3-0.6b"), smoke_mix(),
                        limits, e2e, per_layer, seed=2**31 + 5,
                        seconds=seconds, trace=False,
                        devices=jax.devices(), t_start=time.perf_counter())


@pytest.fixture(scope="module")
def smoke_run():
    return run_smoke()


def test_a_run_at_smoke_geometry_is_correct(smoke_run):
    out = smoke_run
    assert out["correct"] is True
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert out["attempted"] == 12 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["gap_max"]["value"] <= 0.05
    assert out["checks"]["tokens_compared"]["value"] >= 32
    assert out["run"]["compiles_in_window"] == 0


def test_the_memory_peak_counts_the_programs_temporaries(smoke_run):
    """``memory_peak_bytes`` covers the weights, the pool, and the most
    that the decode step or the prefill chunk allocates while it runs."""
    mem = smoke_run["run"]["memory"]
    c = smoke_config("qwen3-0.6b")
    m = ref.dims(c)
    weights = 4 * (m["V"] * m["d"] + m["d"] + m["L"] * (
        2 * m["d"] + m["d"] * m["H"] * m["D"] + 2 * m["d"] * m["K"] * m["D"]
        + m["H"] * m["D"] * m["d"] + 2 * m["D"] + 3 * m["d"] * m["F"]))
    s = SERVING
    pool = 4 * m["L"] * s["slots"] * s["max_len"] * 2 * m["K"] * m["D"]
    assert pool <= mem["resident_bytes"] - weights < 1.1 * pool
    step = mem["program_bytes"]["decode_step"]
    assert step["temp"] > 0
    assert smoke_run["device"]["memory_peak_bytes"] == max(
        mem["peak_bytes_in_use"] or 0, mem["resident_bytes"] + max(
            sum(v.values()) for v in mem["program_bytes"].values()))


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from repro.serve import scheduler

    real = scheduler.sample_tokens

    def off_by_one(logits, keys, **kw):
        return (real(logits, keys, **kw) + 1) % logits.shape[-1]
    monkeypatch.setattr(scheduler, "sample_tokens", off_by_one)
    out = run_smoke()
    assert out["correct"] is False
    assert out["checks"]["gap_max"]["value"] > 0.05


def test_a_prefill_chunk_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    from repro.models import decode
    monkeypatch.setattr(decode, "paged_prefill_chunk",
                        lambda params, cache, *a, **k: cache)
    out = run_smoke()
    assert out["correct"] is False
    assert out["checks"]["gap_max"]["value"] > 0.05


def test_the_command_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run_cell.py"), "--workload",
         "qwen3-0.6b.chat-offline", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode == run_cell.EXIT_NO_CHIP
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def program_logits(c, params, seq, n_prompt):
    """Prefill ``seq[:n_prompt - 1]`` through the program's paged chunks,
    then feed the rest through its paged decode step; the logits of every
    decode step."""
    from repro.models import decode as dec
    cfg = adapter.model_config(c)
    ps, max_len = 16, 64
    cache = dec.init_paged_cache(cfg, 1, max_len, ps, jnp.float32)
    chunk = jax.jit(lambda p, c_, t, n: dec.paged_prefill_chunk(
        p, c_, t, cfg, None, slot=0, count=n))
    step = jax.jit(lambda p, c_, t: dec.paged_decode_step(p, c_, t, cfg,
                                                          None))
    pre = list(seq[:n_prompt - 1])
    for i in range(0, len(pre), ps):
        part = pre[i:i + ps]
        cache = chunk(params, cache,
                      jnp.asarray(part + [0] * (ps - len(part)), jnp.int32),
                      len(part))
    out = []
    for t in seq[n_prompt - 1:]:
        lg, cache = step(params, cache, jnp.asarray([t], jnp.int32))
        out.append(np.asarray(lg[0], np.float32))
    return np.stack(out)


@pytest.mark.parametrize("name", ["qwen3-0.6b"])
def test_reference_matches_the_programs_prefill_and_paged_decode(name):
    """At float32 compute the program's paged prefill and decode give the
    reference's logits to float32 rounding, qk-norm, grouped KV heads and
    the gated MLP included."""
    c = smoke_config(name)
    c["program"] = dict(c["program"], compute_dtype="float32")
    key = jax.random.key(3)
    params = adapter.program_params(c, key)
    seq = np.random.default_rng(0).integers(0, 512, 40).tolist()
    n_prompt = 29                       # two chunks, then 12 decode steps
    got = program_logits(c, params, seq, n_prompt)
    w = ref.init(c, key)
    toks = np.zeros(64, np.int32)
    toks[:len(seq)] = seq
    rows = np.arange(n_prompt - 1, len(seq))
    want = np.asarray(ref.logits(c, w, jnp.asarray(toks),
                                 jnp.asarray(rows, jnp.int32)))
    assert np.max(np.abs(got - want)) < 1e-4 * max(1.0, np.abs(want).max())


# The control at a size a test run can hold: d_model 512, vocab 32768 (a
# vocabulary wide enough for near ties), two layers.  Over seeds 1..12 the
# program's readings were at most 0.0207 and the float8 control's at least
# 0.0851 (CPU, bfloat16 compute), so this size's limit sits at 0.045.
CONTROL_SIZE = dict(hidden_size=512, vocab_size=32768, intermediate_size=1024,
                    head_dim=64)
CONTROL_LIMIT = 0.045


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_is_not_correct(seed):
    c = smoke_config("qwen3-0.6b", **CONTROL_SIZE)
    m = smoke_mix()
    m["arrival"]["requests"] = 16
    m["output_len"].update(median=16, min=8, max=32)
    m["check_tokens"] = 400
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, _, _, _, e2e, per_layer = run_cell.cell_spec(
        bench, "qwen3-0.6b.chat-offline")
    out = run_cell.run(cell, c, m, {"gap_max": CONTROL_LIMIT,
                                    "min_tokens": 1}, e2e, per_layer,
                       seed=seed, seconds=3.0, trace=False,
                       devices=jax.devices(), t_start=time.perf_counter(),
                       control=True)
    assert out["correct"] is True
    assert out["control"]["correct"] is False
    assert out["control"]["checks"]["gap_max"]["value"] > CONTROL_LIMIT
    assert out["control"]["checks"]["tokens_compared"] == out["checks"][
        "tokens_compared"]
