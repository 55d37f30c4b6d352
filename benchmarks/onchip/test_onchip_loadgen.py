"""The traffic generator: deterministic per seed, lengths and gaps drawn
from the mix's stated distributions, tails included, and spread evenly
over consecutive requests."""
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np
import pytest

import loadgen

TRAFFIC = Path(__file__).resolve().parent / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


def mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests_other_seed_other_sizes(name):
    m = mix(name)
    a = loadgen.generate(m, 2**31 + 11, 1000, 30)
    b = loadgen.generate(m, 2**31 + 11, 1000, 30)
    c = loadgen.generate(m, 7, 1000, 30)
    key = [(r.due, r.max_new, r.prompt.tolist()) for r in a]
    assert key == [(r.due, r.max_new, r.prompt.tolist()) for r in b]
    assert [r.max_new for r in a] != [r.max_new for r in c]
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_stated_lognormal_tails_included(name):
    """Pooled over seeds, every request's length is a draw from the
    clipped lognormal: its quantiles match, and the clips are reached
    where the distribution reaches them."""
    m = mix(name)
    reqs = [r for s in range(400)
            for r in loadgen.generate(m, s, 1000, 30)[:5]]
    for field, dist in ((lambda r: len(r.prompt), m["prompt_len"]),
                        (lambda r: r.max_new, m["output_len"])):
        xs = np.array([field(r) for r in reqs])
        assert xs.min() >= dist["min"] and xs.max() <= dist["max"]
        for q in (0.1, 0.25, 0.5, 0.75, 0.9, 0.99):
            want = loadgen.quantile(dist, q)
            assert np.quantile(xs, q) == pytest.approx(want, rel=0.12,
                                                       abs=2)
        top = np.mean(xs == dist["max"])
        u_max = NormalDist().cdf(
            math.log((dist["max"] - 0.5) / dist["median"]) / dist["sigma"])
        assert top == pytest.approx(1 - u_max, abs=0.01)
    # prompt plus output always fits the serving max_len
    assert m["prompt_len"]["max"] + m["output_len"]["max"] <= 2048


def test_prompt_output_and_gap_are_drawn_apart():
    m = mix("shortchat-steady")
    rows = []
    for s in range(4000):
        r = loadgen.generate(m, s, 1000, 10)
        rows.append((len(r[0].prompt), r[0].max_new, r[1].due))
    ranks = np.argsort(np.argsort(np.array(rows), axis=0), axis=0)
    corr = np.corrcoef(ranks.T)
    assert np.all(np.abs(corr[np.triu_indices(3, 1)]) < 0.06)


@pytest.mark.parametrize("n", [8, 16, 64])
def test_consecutive_draws_cover_the_quantiles_evenly(n):
    """Any n consecutive points of a stream leave no gap in [0, 1) wider
    than 3 / n (the golden-ratio sequence's gaps take at most three
    lengths), whatever the seed's shift."""
    for step in loadgen.STEPS:
        for shift in np.random.default_rng(n).random(20):
            for start in (0, 5, 37):
                u = np.sort(loadgen.kronecker(shift, step, start + n)[start:])
                gaps = np.diff(np.concatenate([u, [u[0] + 1]]))
                assert gaps.max() < 3.0 / n


def test_lognormal_quantile_matches_its_definition():
    d = {"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 1,
         "max": 10**6}
    assert loadgen.quantile(d, 0.5) == 100
    assert loadgen.quantile(d, 0.9) == round(
        100 * math.exp(0.5 * NormalDist().inv_cdf(0.9)))
    assert loadgen.quantile(dict(d, max=120), 0.99) == 120


def test_poisson_arrivals_cover_the_window_at_the_stated_rate():
    m = mix("shortchat-steady")
    rate = m["arrival"]["rate_per_s"]
    means = []
    for seed in range(200):
        for seconds in (10, 51):
            due = np.array([r.due for r in
                            loadgen.generate(m, seed, 1000, seconds)])
            assert due[0] == 0.0 and np.all(np.diff(due) > 0)
            assert due[-1] > seconds          # nothing due in the window
        in_window = np.diff(due[due <= 51])
        means.append(in_window.mean())
        assert in_window.mean() == pytest.approx(1 / rate, rel=0.3)
    assert np.mean(means) == pytest.approx(1 / rate, rel=0.03)


def test_backlog_is_due_at_once():
    m = mix("chat-offline")
    reqs = loadgen.generate(m, 1, 1000, 30)
    assert len(reqs) == m["arrival"]["requests"]
    assert all(r.due == 0.0 for r in reqs)
    assert all(0 <= t < 1000 for r in reqs for t in r.prompt)
