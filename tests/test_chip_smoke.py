"""chip_smoke.py on CPU: it refuses a host without a TPU, and its serve
and Pallas-vs-XLA reference phases hold at the smoke geometry, with the
Pallas kernels interpreted (the kernel phase needs a chip: interpreted
kernels emit no Mosaic call)."""
import gc
import importlib.util
from pathlib import Path

import jax
import pytest

PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_host_without_a_tpu(cs, capsys):
    assert cs.main() == 1
    out = capsys.readouterr().out
    assert "FAIL: default device is 'cpu', not a TPU" in out
    assert '"ok"' not in out
    assert "model" not in out          # failed before building the model


def test_serve_and_reference_phases_at_smoke_geometry(cs, monkeypatch,
                                                      capsys):
    monkeypatch.setattr(cs, "GEN", 6)
    monkeypatch.setattr(cs, "PROMPT_LENS", (8, 17, 24, 33, 40, 48, 57, 64))
    monkeypatch.setattr(cs, "serve_args", lambda slots: cs.serve.parse_args(
        ["--arch", cs.ARCH, "--smoke", "--requests", str(slots), "--gen",
         "6", "--max-len", "128", "--prompt-len", "64"]))
    with cs.vx.use("pallas"):          # the chip's default lowering
        args, cfg, params, server = cs.build(cs.SLOTS)
        replay = cs.phase_serve(args, cfg, server, jax.devices()[0])
    del server
    gc.collect()
    cs.phase_reference(cfg, params, *replay)
    out = capsys.readouterr().out
    assert "tokens generated: 48" in out
    assert "reference (XLA lowering, 2 slots)" in out
    assert out.count("streams identical; first-decode logits "
                     "bit-identical=True") == 2
