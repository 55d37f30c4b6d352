"""The qwen3-moe-30b-a3b cell's harness on the CPU at smoke geometry: the
adapter lays the reference's weights out as the program's initialiser
does, the reference's shares add up to the uncut layer, the program's
prefill and paged decode give the reference's logits, a whole run reads
``correct``, and runs with the expert layer broken underneath do not."""
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run_cell
import test_onchip_harness as harness
from adapters import moe_decoder as adapter
from references import moe_decoder as ref

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CELL = "qwen3-moe-30b-a3b.chat-offline"
# experts 4..7 of 16, top 4; experts 512 wide so that the expert layer
# moves the logits at this size
SMOKE = dict(hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, moe_intermediate_size=512,
             vocab_size=512, num_experts=4, num_experts_per_tok=4,
             first_expert=4, published={"num_hidden_layers": 48,
                                        "num_experts": 16})
SERVING = dict(slots=4, max_len=128, page_size=16, kv_dtype="float32",
               chunk_pages=2)
# Over seeds 1..3 the program read at most 0.0044 (CPU, bfloat16 compute);
# a held expert redrawn read 0.027-0.117, renormalising over the held
# experts only 0.15-0.30.
LIMIT = 0.015


def smoke_config(**over):
    c = json.loads((HERE / "configs" / "qwen3-moe-30b-a3b.json").read_text())
    c.update(SMOKE, serving=dict(SERVING), **over)
    return c


def smoke_mix():
    m = json.loads((HERE / "traffic" / "chat-offline.json").read_text())
    m["arrival"]["requests"] = 12
    m["prompt_len"].update(median=24, min=8, max=64)
    m["output_len"].update(median=8, min=4, max=16)
    m["check_tokens"] = 32
    return m


def run_smoke(seed):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, _, _, _, e2e, per_layer = run_cell.cell_spec(bench, CELL)
    return run_cell.run(cell, smoke_config(), smoke_mix(),
                        {"gap_max": LIMIT, "min_tokens": 1}, e2e, per_layer,
                        seed=seed, seconds=2.0, trace=False,
                        devices=jax.devices(), t_start=time.perf_counter())


def test_the_adapter_lays_out_the_reference_weights():
    """``program_params`` checks its tree against ``init_params``; its
    values are the reference's: held expert h is expert first + h, the
    router float32 and over all experts."""
    c = smoke_config()
    key = jax.random.key(5)
    params = adapter.program_params(c, key)
    w = ref.init(c, key)
    moe = params["blocks"]["pos0"]["moe"]
    assert moe["router"].dtype == jnp.float32
    assert moe["router"].shape == (2, 128, 16)
    assert moe["wg"].shape == (2, 4, 128, 512)
    for mine, theirs in (("wg", "w_gate"), ("wu", "w_up"),
                         ("wo", "w_down"), ("router", "router")):
        np.testing.assert_array_equal(
            np.asarray(moe[mine], np.float32),
            np.asarray(w["layers"][theirs].astype(moe[mine].dtype),
                       np.float32))
    one = ref.init_layer(c, key, 1)
    other = ref.init_layer(smoke_config(first_expert=5), key, 1)
    np.testing.assert_array_equal(one["w_gate"][1:], other["w_gate"][:3])


def test_the_reference_shares_add_up_to_the_uncut_layer():
    """The reference's expert layer of the four disjoint shares of 16
    experts sums to its layer with all 16 held (float32 summation order
    only)."""
    key = jax.random.key(2)
    whole = smoke_config(num_experts=16, first_expert=0)
    m = ref.dims(whole)
    w = ref.init_layer(whole, key, 0)
    x = jax.random.normal(jax.random.key(1), (24, m["d"]))
    with jax.default_matmul_precision("highest"):
        y = ref._experts(m, False, x, w) - x
        parts = []
        for r in range(4):
            c = smoke_config(first_expert=4 * r)
            wr = ref.init_layer(c, key, 0)
            np.testing.assert_array_equal(wr["router"], w["router"])
            parts.append(ref._experts(ref.dims(c), False, x, wr) - x)
    np.testing.assert_allclose(sum(parts), y, rtol=1e-5, atol=1e-6)


def test_reference_matches_the_programs_prefill_and_paged_decode(
        monkeypatch):
    """At float32 compute the program's paged prefill and decode give the
    reference's logits to float32 rounding: routing, renormalisation over
    the top 4 of 16 and the held share included."""
    monkeypatch.setattr(harness, "adapter", adapter)
    c = smoke_config()
    c["program"] = dict(c["program"], param_dtype="float32",
                        compute_dtype="float32")
    key = jax.random.key(3)
    params = adapter.program_params(c, key)
    seq = np.random.default_rng(0).integers(0, 512, 40).tolist()
    n_prompt = 29                       # two chunks, then 12 decode steps
    got = harness.program_logits(c, params, seq, n_prompt)
    w = ref.init(c, key)
    toks = np.zeros(64, np.int32)
    toks[:len(seq)] = seq
    rows = np.arange(n_prompt - 1, len(seq))
    want = np.asarray(ref.logits(c, w, jnp.asarray(toks),
                                 jnp.asarray(rows, jnp.int32)))
    assert np.max(np.abs(got - want)) < 1e-4 * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_run_at_smoke_geometry_is_correct(seed):
    out = run_smoke(seed)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert out["failed"] == 0 and out["run"]["compiles_in_window"] == 0
    assert out["checks"]["gap_max"]["value"] <= LIMIT


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_held_expert_altered_is_not_correct(monkeypatch, seed):
    """Held expert 0's weights redrawn: the tokens routed to it get
    another expert's output."""
    real = adapter.program_params

    def altered(c, key):
        params = real(c, key)
        moe = params["blocks"]["pos0"]["moe"]
        for name in ("wg", "wu", "wo"):
            a = moe[name]
            moe[name] = a.at[:, 0].set(0.02 * jax.random.normal(
                jax.random.key(99), a.shape[:1] + a.shape[2:], a.dtype))
        return params
    monkeypatch.setattr(adapter, "program_params", altered)
    out = run_smoke(seed)
    assert out["correct"] is False
    assert out["checks"]["gap_max"]["value"] > LIMIT


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_renormalising_over_the_held_experts_only_is_not_correct(
        monkeypatch, seed):
    """The held experts' weights renormalised among themselves instead of
    over all top-k experts, those held elsewhere included."""
    from repro.models import decode
    real = decode.moe_ffn_local

    def held_only(router, wg, wu, wo, x, spec, **kw):
        y, aux, units = real(router, wg, wu, wo, x, spec, **kw)
        probs = jax.nn.softmax(
            (x @ router.astype(x.dtype)).astype(jnp.float32), -1)
        topw, topi = jax.lax.top_k(probs, spec.top_k)
        topw = topw / jnp.sum(topw, -1, keepdims=True)
        mine = (topi >= spec.first_expert) & (
            topi < spec.first_expert + spec.n_held)
        s = jnp.sum(jnp.where(mine, topw, 0.0), -1)
        return ((y / jnp.where(s > 0, s, 1.0)[:, None]).astype(y.dtype),
                aux, units)
    monkeypatch.setattr(decode, "moe_ffn_local", held_only)
    out = run_smoke(seed)
    assert out["correct"] is False
    assert out["checks"]["gap_max"]["value"] > LIMIT
