"""From a profiler trace to the numbers the per-layer metrics read.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation the device ran.  The client's host spans
(``bench.*``, see ``client``) sit on a host plane on the same clock, and
``bench.window`` marks the measured window.

* busy: the union of the operation intervals inside the window, averaged
  over the device planes; idle share is 1 - busy / window;
* ``op_seconds``: device seconds per operation name inside the window;
* idle gaps: the intervals of the window in which no operation ran, each
  named by the innermost host span that covers its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
GAPS_KEPT = 10


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    op_seconds: dict            # name -> device seconds in the window
    gaps: list                  # (seconds, host span), the longest ones
    devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(profile) -> Summary:
    """``profile``: a ``ProfileData`` (or anything with its planes)."""
    host_spans, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(e.name, e.start_ns, e.end_ns)
                                    for e in line.events])
        else:
            for line in plane.lines:
                host_spans += [(e.name, e.start_ns, e.end_ns)
                               for e in line.events
                               if e.name.startswith("bench.")]
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not windows or not devices:
        raise ValueError("trace holds no bench.window span or no device "
                         "operations")
    w0, w1 = windows[0]
    spans = [(n, s, e) for n, s, e in host_spans if n != WINDOW_SPAN]
    busy, op_s, gaps = 0.0, {}, []
    for i, ops in enumerate(devices):
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                  if e > w0 and s < w1]
        for n, s, e in inside:
            op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9 / len(devices)
        merged = _union([(s, e) for _, s, e in inside])
        busy += sum(e - s for s, e in merged) * 1e-9 / len(devices)
        if i == 0:                     # gaps of the first device
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [((e - s) * 1e-9, _span_at(spans, (s + e) / 2))
            for s, e in gaps[:GAPS_KEPT]]
    return Summary((w1 - w0) * 1e-9, busy, op_s, gaps, len(devices))


def _span_at(spans, t) -> str:
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "none"


def load(trace_dir: str) -> Summary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(trace_dir)))


def label(op: str) -> str:
    """A short name for an ``XLA Ops`` event, whose name is the whole HLO
    instruction: ``%name opcode output-shape``."""
    head, _, rest = op.partition(" = ")
    if rest.startswith("("):                 # a tuple-shaped output
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, after = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, after = rest.partition(" ")
    opcode = after.split("(")[0]
    if len(shape) > 80:
        shape = shape[:77] + "..."
    return " ".join(x for x in (head, opcode, shape) if x)


def breakdown(summary: Summary, k: int = 10) -> dict:
    """The device operations that took most time (an operation that holds
    others, such as a ``while``, counts their time too) and the longest
    idle gaps by what the host was doing."""
    ops = sorted(summary.op_seconds.items(), key=lambda kv: -kv[1])[:k]
    return {"device_ops": [[label(n), s] for n, s in ops],
            "idle_gaps": [[n, s] for s, n in summary.gaps[:k]]}
