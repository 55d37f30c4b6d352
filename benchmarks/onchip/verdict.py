"""Whether what the timed path served is correct.

After the window closes, a sample of the requests the program finished,
drawn from the seed and always holding the one with the most served
tokens, is run through the plain float32 reference: one forward pass over
each prompt followed by its served tokens.  At every served position the
number compared is the gap by which the served token's reference logit
lies below the reference's best logit there; a request's reading is its
widest gap, and the run's is the widest over the sample.  A greedy program
that computes what the reference computes serves the reference's argmax,
or a token within its own rounding of it.

The control (``quant="fp8"``) puts the reference in the program's place at
float8: at the same positions of the same prompts and served tokens it
reads the gap of the token that the float8 pass puts first, and that gap
goes through the same checks, against the same limits, as the program's.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np


def sample(log, seed: int, budget: int) -> list:
    """Finished requests: the longest, then others in a seeded order until
    ``budget`` served tokens are in the sample."""
    done = [r for r in log.reqs if r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.tokens), r.idx))
    out, n = [done[0]], len(done[0].tokens)
    rest = done[1:]
    for j in np.random.default_rng(seed).permutation(len(rest)):
        if n >= budget:
            break
        out.append(rest[j])
        n += len(rest[j].tokens)
    return out


@jax.jit
def _gaps(ref_logits, tokens):
    """Per row: the best reference logit minus the token's."""
    return jnp.max(ref_logits, -1) - jnp.take_along_axis(
        ref_logits, tokens[:, None], -1)[:, 0]


def verdict(gap, compared: int, short: int, failed: int,
            limits: dict) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit."""
    checks = {
        "gap_max": {"value": gap, "limit": limits["gap_max"]},
        "tokens_compared": {"value": compared, "limit": limits["min_tokens"]},
        "wrong_length": {"value": short, "limit": 0},
        "failed": {"value": failed, "limit": 0},
    }
    correct = (gap is not None and gap <= limits["gap_max"]
               and compared >= limits["min_tokens"] and short == 0
               and failed == 0)
    return correct, checks


def judge(log, c: dict, key, mix: dict, limits: dict, seed: int, *,
          control: bool = False) -> dict:
    """``correct``, the numbers compared with their limits (``checks``),
    the requests failed, and each compared request's reading.  Besides the
    widest gap: every finished request has its drawn length, none failed
    or timed out, and at least ``min_tokens`` served tokens are compared.
    With ``control``, ``control`` holds the float8 control's ``correct``
    and ``checks``, judged by the same rule at the same positions.  Run it
    once the program's state is freed: it makes the reference's weights
    from ``key`` on the device."""
    from e2e_metrics import due_in_window
    due = due_in_window(log)
    failed = sum(r.state in ("failed", "timed_out") for r in due)
    short = sum(1 for r in due if r.tokens is not None
                and len(r.tokens) != r.max_new)
    ref = importlib.import_module(f"references.{c['reference']}")
    weights = ref.init(c, key)
    read = readings(c, weights, sample(log, seed, mix["check_tokens"]),
                    mix["output_len"]["max"], control=control)
    del weights
    compared = sum(x["served"] for x in read)

    def widest(field):
        return max((x[field] for x in read), default=None)
    correct, checks = verdict(widest("gap"), compared, short, failed, limits)
    out = {"correct": correct, "checks": checks, "failed": failed,
           "readings": read}
    if control:
        ok, cchecks = verdict(widest("control_gap"), compared, short, failed,
                              limits)
        out["control"] = {"correct": ok, "checks": cchecks}
    return out


def readings(c: dict, weights: dict, reqs: list, rows: int, *,
             control: bool = False) -> list[dict]:
    """One reading per request: ``gap`` (the widest, served tokens) and,
    with ``control``, ``control_gap`` (the float8 reference's)."""
    ref = importlib.import_module(f"references.{c['reference']}")
    S = c["serving"]["max_len"]
    out = []
    for r in reqs:
        P, n = len(r.prompt), len(r.tokens)
        seq = np.zeros(S, np.int32)
        full = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        seq[:len(full)] = full
        pos = np.zeros(rows, np.int32)
        pos[:n] = np.arange(P - 1, P - 1 + n)
        tok = np.zeros(rows, np.int32)
        tok[:n] = r.tokens
        lg = ref.logits(c, weights, jnp.asarray(seq), jnp.asarray(pos))
        rec = {"idx": r.idx, "prompt": P, "served": n,
               "gap": float(np.max(np.asarray(_gaps(lg, tok))[:n]))}
        if control:
            lc = ref.logits(c, weights, jnp.asarray(seq), jnp.asarray(pos),
                            quant="fp8")
            rec["control_gap"] = float(np.max(np.asarray(
                _gaps(lg, jnp.argmax(lc, -1)))[:n]))
        out.append(rec)
    return out
