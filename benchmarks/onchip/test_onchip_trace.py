"""The trace reduction on a small recorded trace (an XSpace written by
hand: one TPU plane, one host plane with the client's spans)."""
import pytest
from jax.profiler import ProfileData

import trace_reduce

# Device ops (ns): fusion 1000-6000, kernel 8000-9000, fusion 8500-9500
# (overlaps the kernel), copy 15000-25000 (crosses the window's close).
# Host spans (ns): window 0-20000, tick 0-12000, readout 12000-13000,
# wait 13000-20000.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 7500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 14000000 duration_ps: 10000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "_deint_plan_kernel" } }
  event_metadata { key: 3 value { id: 3 name: "copy.2" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 13000000 duration_ps: 7000000 }
    events { metadata_id: 5 offset_ps: 1000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.tick" } }
  event_metadata { key: 3 value { id: 3 name: "bench.readout" } }
  event_metadata { key: 4 value { id: 4 name: "bench.wait" } }
  event_metadata { key: 5 value { id: 5 name: "unrelated" } }
}
"""


@pytest.fixture
def summary():
    return trace_reduce.reduce(ProfileData.from_text_proto(TRACE))


def test_busy_is_the_union_of_device_ops_inside_the_window(summary):
    assert summary.window_s == pytest.approx(20e-6)
    # 1000-6000, 8000-9500, 15000-20000 (clipped at the close)
    assert summary.busy_s == pytest.approx(11.5e-6)
    assert summary.idle_share == pytest.approx(1 - 11.5 / 20)
    assert summary.devices == 1


def test_device_seconds_per_op_name(summary):
    assert summary.op_seconds["fusion.1"] == pytest.approx(6e-6)
    assert summary.op_seconds["_deint_plan_kernel"] == pytest.approx(1e-6)
    assert summary.op_seconds["copy.2"] == pytest.approx(5e-6)
    assert set(summary.op_seconds) == {"fusion.1", "_deint_plan_kernel",
                                       "copy.2"}    # modules are not ops


def test_idle_gaps_are_named_by_the_host_span_over_them(summary):
    # gaps 0-1000 and 6000-8000 lie in the tick; 9500-15000 has its
    # midpoint (12250) in the readout
    assert summary.gaps == [(pytest.approx(5.5e-6), "bench.readout"),
                            (pytest.approx(2e-6), "bench.tick"),
                            (pytest.approx(1e-6), "bench.tick")]
    bd = trace_reduce.breakdown(summary)
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(6e-6)]
    assert bd["idle_gaps"][0] == ["bench.readout", pytest.approx(5.5e-6)]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce(ProfileData.from_text_proto(
            TRACE.replace('"bench.window"', '"bench.other"')))
