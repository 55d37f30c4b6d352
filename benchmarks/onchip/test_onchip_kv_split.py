"""``kv_split_roofline.offline`` on a small recorded trace (an XSpace
written by hand: one TPU plane with a decode step and a prefill chunk,
one host plane with the client's and the program's spans)."""
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

import run_cell
import trace_reduce
import workcount
from client import Log, ReqLog, TickLog

HERE = Path(__file__).resolve().parent

# Device (ns): module jit_decode_step(1) 1000-9000 holds kv_split.1
# 2000-6000 and a fusion that reads its output 6000-7000; module
# jit_prefill_chunk(2) 10000-15000 holds the chunk's own split,
# closed_call.57, 11000-12000.  Host (ns): window 0-20000, one bench.tick
# 0-16000 holding serve.tick 500-15500, whose serve.decode.readback
# 8000-9500 holds a serve.host_sync 8500-9000; a JAX
# backend_compile_and_load 15600-19000.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 11000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 8000000 }
    events { metadata_id: 5 offset_ps: 10000000 duration_ps: 5000000 }
  }
  event_metadata { key: 1 value { id: 1 name:
    "%kv_split.1 = (f32[64,128]{1,0}, f32[64,128]{1,0}) custom-call(%select_fusion)" } }
  event_metadata { key: 2 value { id: 2 name:
    "%fusion.3 = f32[64,128]{1,0} fusion(%kv_split.1), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name:
    "%closed_call.57 = (f32[16,128]{1,0}, f32[16,128]{1,0}) custom-call(%copy.1)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_decode_step(1)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_prefill_chunk(2)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 16000000 }
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 15000000 }
    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 1500000 }
    events { metadata_id: 5 offset_ps: 8500000 duration_ps: 500000 }
    events { metadata_id: 6 offset_ps: 15600000 duration_ps: 3400000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.tick" } }
  event_metadata { key: 3 value { id: 3 name: "serve.tick" } }
  event_metadata { key: 4 value { id: 4 name: "serve.decode.readback" } }
  event_metadata { key: 5 value { id: 5 name: "serve.host_sync" } }
  event_metadata { key: 6 value { id: 6 name: "backend_compile_and_load" } }
}
"""

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return json.loads((HERE / "configs" / "qwen3-0.6b.json").read_text())


def view(trace, reqs):
    log = Log(0.0, 20e-6, reqs, [TickLog(0.0, 16e-6)], num_pages=40)
    return run_cell.View("qwen3-0.6b.chat-offline", config(), {}, log,
                         trace, PEAKS)


def req(prompt_len, decoded):
    r = ReqLog(0, 0.0, np.zeros(prompt_len, np.int32), max_new=decoded)
    r.tok_t = [1e-5] * decoded
    return r


@pytest.fixture
def summary():
    return trace_reduce.reduce(ProfileData.from_text_proto(TRACE))


def reader():
    spec = importlib.util.spec_from_file_location(
        "kv_split_reader", HERE / "metrics" / "kv_split_roofline.offline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_split_is_found_by_its_own_name(summary):
    # the fusion that reads %kv_split.1 and the prefill chunk's unnamed
    # split are not the split
    assert reader().split_seconds(summary) == pytest.approx(4e-6)


def test_decoded_tokens_read_their_live_context():
    # prompts of 5 and 3 tokens: contexts 4, 5, 6 and 2, 3
    log = view(None, [req(5, 3), req(3, 2)]).log
    assert reader().decode_context_tokens(log) == 4 + 5 + 6 + 2 + 3


def test_roofline_share_of_the_split(summary):
    v = view(summary, [req(5, 3), req(3, 2)])
    least = 2 * workcount.kv_bytes_per_token(v.config) * 20
    assert run_cell.read_metric("kv_split_roofline.offline", v) == \
        pytest.approx(100 * least / (4e-6 * 819e9))


def test_a_program_that_does_not_name_the_split_reads_none(summary):
    older = TRACE.replace("%kv_split.1", "%_lambda_.1")
    v = view(trace_reduce.reduce(ProfileData.from_text_proto(older)),
             [req(5, 3)])
    assert run_cell.read_metric("kv_split_roofline.offline", v) is None
    assert run_cell.read_metric(
        "kv_split_roofline.offline", view(None, [req(5, 3)])) is None


def test_the_programs_spans_leave_the_existing_reduction_as_it_was(summary):
    """The serve.* spans and JAX's compile events are not bench.* spans:
    busy time and the gaps' names come out as they did without them."""
    assert summary.busy_s == pytest.approx(6e-6)
    # 12000-20000 has its midpoint inside the compile event, 7000-11000
    # inside the readback: both are still named by bench.tick
    assert summary.gaps == [(pytest.approx(8e-6), "bench.tick"),
                            (pytest.approx(4e-6), "bench.tick"),
                            (pytest.approx(2e-6), "bench.tick")]
