"""End-to-end arithmetic over the client's log (host clock, seconds).

* ``tokens_per_s``: output tokens delivered in the window over the
  window's length.
* ``ttft``: for every request due in the window, its first token's time
  minus its due time; a request with no token by the window's close counts
  at its wait so far.
* ``tpot``: for every request with two or more tokens in the window,
  (last token - first token) / (tokens - 1).

Percentiles interpolate linearly between order statistics (numpy's
default).
"""
from __future__ import annotations

import numpy as np


def percentile(xs, q: float) -> float | None:
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


def due_in_window(log) -> list:
    return [r for r in log.reqs if r.due <= log.t_close]


def tokens_per_s(log) -> float:
    n = sum(sum(t <= log.t_close for t in r.tok_t) for r in log.reqs)
    return n / log.window_s


def ttfts(log) -> list[float]:
    out = []
    for r in due_in_window(log):
        first = r.tok_t[0] if r.tok_t and r.tok_t[0] <= log.t_close else None
        out.append((first if first is not None else log.t_close) - r.due)
    return out


def tpots(log) -> list[float]:
    out = []
    for r in log.reqs:
        ts = [t for t in r.tok_t if t <= log.t_close]
        if len(ts) >= 2:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out


def end_to_end(log, setup_s: float) -> dict:
    """Every end-to-end metric the log supports, by name."""
    out = {"setup_s": (setup_s, "s"),
           "tokens_per_s": (tokens_per_s(log), "tokens/s")}
    tp = tpots(log)
    if tp:
        out["tpot_p90_ms"] = (1e3 * percentile(tp, 90), "ms")
    return out
