"""``benchmarks/moe_layer_chip.py`` at smoke size on the CPU: the counters
read after a served window, the expert layer's share of both programs,
and the routing replay against the plain reference."""
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))

import moe_layer_chip  # noqa: E402
from test_onchip_moe import smoke_config, smoke_mix  # noqa: E402


def test_the_three_parts_read_the_expert_layer(capsys):
    c, mix = smoke_config(), smoke_mix()
    assert moe_layer_chip.run(c, mix, seed=3, seconds=2.0, iters=1,
                              replay=[0, 1]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    counted, layer, *replayed = lines
    moe = counted["moe"]
    assert counted["part"] == "counters" and moe["dropped"] == 0
    assert moe["routed"] == sum(moe["per_expert"]) > 0
    assert counted["decode_steps"] > 0 and counted["prefill_chunks"] > 0
    assert layer["part"] == "layer"
    for k in ("decode_step", "prefill_chunk"):
        assert layer["with_ms"][k] > 0 and layer["without_ms"][k] > 0
    assert layer["layer_share"].keys() == {"decode_step", "prefill_chunk"}
    assert "window_layer_share" in layer
    assert [r["idx"] for r in replayed] == [0, 1]
    for r in replayed:
        assert r["part"] == "routes" and 0 <= r["widest_at"] < r["served"]
        assert np.isfinite(r["gap_max"]) and r["gap_max"] >= 0
        assert r["gap_max"] == max(r["gap_max_held_agree"],
                                   r["gap_max_held_flipped"])
        assert (r["positions_held_flipped"] <= r["positions_flipped"]
                <= r["served"])
