"""FIELD=2 KV split: the least bytes the decode steps' whole-step K|V
split needs, over the device seconds of the ``kv_split`` calls in the
window times the chip's peak HBM bytes/s, in %.

The least bytes: for every token a request decoded in the window, the
live context the step read (the prompt but its last token, plus the
tokens generated before this one) read once as K|V rows and written once
as K and V, over every layer at the pool's dtype.  The split of today
moves every slot's whole ``max_len`` row, so the share stays far below
100%; a split that moves only the live rows raises it.

``kv_split`` is the HLO instruction that
``kernels/kv_interleaved.split_kv_step`` names (``%kv_split.<n>``); the
instruction's own name is matched, not its operands.  A trace without it,
from a program that does not name the split, gives None."""
import re

import workcount

KV_SPLIT = re.compile(r"%?kv_split(\.\d+)?")


def split_seconds(trace) -> float:
    return sum(s for op, s in trace.op_seconds.items()
               if KV_SPLIT.fullmatch(op.partition(" = ")[0].strip()))


def decode_context_tokens(log) -> int:
    """Sum over decoded tokens of the context the step read: a request
    with a P-token prompt reads P - 1 + j tokens for its j-th (from 0)."""
    total = 0
    for r in log.reqs:
        n, p = len(r.tok_t), len(r.prompt) - 1
        total += n * p + n * (n - 1) // 2
    return total


def read(view):
    if view.trace is None:
        return None
    secs = split_seconds(view.trace)
    if not secs:
        return None
    b = 2 * workcount.kv_bytes_per_token(view.config) \
        * decode_context_tokens(view.log)
    return 100.0 * b / (secs * view.peaks["hbm_bytes_per_s"])
