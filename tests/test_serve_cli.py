"""The serve CLI's exit contract in clean serving, and where the compile
cache goes.  Runs in-process at the smoke geometry; ``run`` (unlike
``main``) leaves the persistent compile cache off."""
import re
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache, serve

SMOKE = ["--arch", "qwen3-0.6b", "--smoke", "--requests", "2",
         "--prompt-len", "6", "--gen", "3", "--max-len", "32",
         "--page-size", "4"]


def test_clean_serve_exits_zero_when_every_request_finishes(capsys):
    assert serve.run(serve.parse_args(SMOKE)) == 0
    out = capsys.readouterr().out
    assert f"tok/s on {serve.device_label()}" in out
    assert out.count("finished") == 2


def test_summary_line_prints_the_serve_path_counters(capsys):
    """The summary line carries the scheduler's decode steps and its
    device->host reads: every decode step reads its sampled tokens back,
    and every prefill chunk reads the free page count first."""
    assert serve.run(serve.parse_args(SMOKE)) == 0
    m = re.search(r"prefill_chunks=(\d+) decode_steps=(\d+) "
                  r"host_syncs=(\d+) ", capsys.readouterr().out)
    chunks, steps, syncs = map(int, m.groups())
    assert steps == 3 and syncs >= steps + chunks


def test_summary_line_prints_the_expert_layer_counters(capsys):
    """A model with held-range expert layers: the summary line carries
    the units routed to the held experts, those dropped (none: the serve
    path is dropless) and those per held expert, which sum to the
    routed."""
    args = serve.parse_args(["--arch", "qwen3-moe-30b-a3b"] + SMOKE[2:])
    assert serve.run(args) == 0
    m = re.search(r"moe_routed=(\d+) moe_dropped=(\d+) "
                  r"moe_per_expert=\[([\d, ]+)\]", capsys.readouterr().out)
    routed, dropped = int(m[1]), int(m[2])
    per_expert = [int(v) for v in m[3].split(",")]
    assert routed > 0 and dropped == 0
    assert len(per_expert) == 4 and sum(per_expert) == routed


def test_clean_serve_exits_nonzero_when_tick_cap_hit(capsys, monkeypatch):
    monkeypatch.setattr(serve, "tick_cap", lambda args: 1)
    assert serve.run(serve.parse_args(SMOKE)) == serve.EXIT_UNFINISHED
    assert "tick cap 1 hit" in capsys.readouterr().out


@pytest.mark.parametrize("replicas", ["1", "2"])
def test_clean_serve_exits_nonzero_when_a_request_fails(capsys, replicas):
    """A prompt longer than max_len ends FAILED at submit: clean serving
    must not report success, on one replica or behind the fleet."""
    args = serve.parse_args(SMOKE + ["--prompt-len", "40",
                                     "--replicas", replicas])
    assert serve.run(args) == serve.EXIT_UNFINISHED
    assert "ended failed" in capsys.readouterr().out


def test_unfinished_flags_short_streams():
    from repro.serve.lifecycle import Request, RequestState
    done = Request(prompt=[1, 2], max_new_tokens=3, tokens=[1, 2, 7, 8, 9])
    short = Request(prompt=[1, 2], max_new_tokens=3, tokens=[1, 2, 7])
    for r in (done, short):
        r.to(RequestState.PREFILLING)
        r.to(RequestState.RUNNING)
        r.to(RequestState.FINISHED)
    assert serve.unfinished([done], 3) == []
    assert serve.unfinished([short], 3) == [
        f"req {short.rid} generated 1 of 3 tokens"]


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    calls = []
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    root = Path(__file__).resolve().parents[1]
    assert compile_cache.enable() == str(root / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir",
                      str(root / ".jax_cache"))]


def test_compile_cache_leaves_a_set_directory_to_jax(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    assert compile_cache.enable() == str(tmp_path)
    assert calls == []
