"""The one traffic generator: a mix's data file in, seeded requests out.

A mix (``traffic/<mix>.json``) gives:

* ``arrival``: ``{"process": "backlog", "requests": N}`` queues N requests
  before the first tick; ``{"process": "poisson", "rate_per_s": r}`` is an
  open loop at r requests a second;
* ``prompt_len`` and ``output_len``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}``, clipped to [min, max].

Every length and every inter-arrival gap is drawn from its distribution by
inversion, ``x_i = F^-1(u_i)``, with ``u_i = frac(s + i * alpha)``: a
Kronecker sequence (randomised quasi-Monte Carlo) whose shift ``s`` is
drawn uniformly from the run's seed, one shift and one irrational step
``alpha`` for each of the three streams.  So each request's prompt length,
output length and gap is, on its own, an exact draw from the stated
distribution, independent of the other two, tails and clips included; and
any run of consecutive requests covers the quantiles evenly, so a window
that sees only its first few tens of requests sees close to the stated
mix on every seed.  The seed also draws every prompt token, uniformly over
the vocabulary.  ``max_new_tokens`` is the drawn output length, and
generation is greedy, so every request's length is fixed by the draw.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

#: The Kronecker steps of the prompt, output and gap streams: fractional
#: parts of the golden ratio, sqrt(2) and sqrt(3), rationally independent
#: so the three streams are equidistributed jointly.
STEPS = ((math.sqrt(5) - 1) / 2, math.sqrt(2) - 1, math.sqrt(3) - 1)


@dataclasses.dataclass(frozen=True)
class Req:
    idx: int
    due: float              # seconds after the window opens
    prompt: np.ndarray      # int32 token ids
    max_new: int


def quantile(dist: dict, u: float) -> int:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    return int(min(max(round(x), dist["min"]), dist["max"]))


def kronecker(shift: float, step: float, n: int) -> np.ndarray:
    """``frac(shift + i * step)`` for i < n: uniform on [0, 1) each, and
    evenly spread over any run of consecutive i."""
    return np.mod(shift + np.arange(n) * step, 1.0)


def count(mix: dict, seconds: float) -> int:
    """Requests the mix sends: the backlog, or enough Poisson arrivals
    that the last is due well after a window of ``seconds`` closes."""
    arr = mix["arrival"]
    if arr["process"] == "backlog":
        return int(arr["requests"])
    if arr["process"] == "poisson":
        return 2 * int(math.ceil(arr["rate_per_s"] * seconds)) + 16
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def generate(mix: dict, seed: int, vocab: int, seconds: float) -> list[Req]:
    rng = np.random.default_rng(seed)
    n = count(mix, seconds)
    up, uo, ug = (kronecker(s, a, n) for s, a in zip(rng.random(3), STEPS))
    if mix["arrival"]["process"] == "poisson":
        gaps = -np.log1p(-ug) / mix["arrival"]["rate_per_s"]
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])  # first at open
    else:
        due = np.zeros(n)
    return [Req(i, float(due[i]),
                rng.integers(0, vocab, quantile(mix["prompt_len"], up[i]),
                             dtype=np.int32),
                quantile(mix["output_len"], uo[i]))
            for i in range(n)]
