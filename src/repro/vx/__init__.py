"""repro.vx — the declarative vector-access API (EARTH's one datapath).

The paper's core claim is a *single* architectural path for all vector
memory access: strided gather/scatter, segment transposition, and
compaction all route through one coalescer + shift network.  ``vx`` is
that claim as an API — one spec type, four verbs, one policy:

    from repro import vx

    spec = vx.Strided(n=64, stride=4, offset=2, vl=8)
    dense = vx.gather(spec, window)                    # strided load
    win2  = vx.scatter(spec, window, dense)            # strided store

    k, v  = vx.transpose(vx.Segment(n=2 * d, fields=2), kv_beat)
    beat  = vx.transpose(vx.Segment(n=2 * d, fields=2), [k, v])

    packed, pv = vx.compact(vx.Compact(n=T), mask, rows)
    ids        = vx.compact(vx.Compact(n=T, cap=C), mask)   # MoE dispatch

    # runtime (traced) stride -> plan-bank lax.switch dispatch
    out = vx.gather(vx.Strided(n=64, stride=vx.BANK, vl=8), win, stride=s)

    # whole-step batched forms (one launch, one mask operand)
    outs = vx.gather_many([spec_a, spec_b], windows)
    kvs  = vx.gather_many(vx.Segment(n=2 * d, fields=2), kv_caches)

    # paged KV pool: geometry is compiled state, the page table is a
    # runtime operand (one cached program serves every request)
    pg   = vx.Paged(page_size=16, pages=8, trail=2)
    seqs = vx.gather(pg, pool, table=tables)             # paged read
    pool = vx.scatter(pg, pool, beats, table=tables, pos=pos)  # append
    alls = vx.gather_many(pg, pools, table=tables)       # ONE program

Lowering is policy-driven, never a per-call ``impl=`` string:

    with vx.use("pallas"):          # or vx.use(Policy(...)) / env default
        ...                         # every verb in scope lowers to Pallas

Resolution order: explicit ``policy=`` arg > innermost ``vx.use`` scope >
``vx.Policy.default()`` (the ``REPRO_VX_IMPL`` env var, else platform).
Plans and lowered executors are memoized in ONE spec-keyed LRU
(:data:`vx.PLANS`) whose keys include dtype and vl.

Every verb lowers through ONE explicit pipeline (PR 4):
**spec** (frozen AccessSpec) -> **plan** (compiled shift plans,
core/shiftplan.py) -> **program** (routed transactions with placement
annotations, ``vx.program``).  Passing ``shard=vx.Shard(axes, axis,
mesh)`` lowers the access shard-locally under ``shard_map`` — per-shard
offset-rebased plans for strided patterns, local lane permutation for
segment transposition — so a sharded buffer is never sliced globally.
Compiled programs are memoized in ``vx.PLANS`` under keys that include
dtype, vl, impl AND the shard layout.

The legacy entry points (``kernels/ops.py``, ``core/drom.py``) survive as
deprecated shims delegating here; internal code must not use them (CI
escalates the shims' DeprecationWarnings to errors).
"""
from repro.vx import lower, program
from repro.vx._dispatch import (compact, gather, gather_many, scatter,
                                scatter_many, transpose, warm)
from repro.vx.cache import PLANS, SEGMENT_LOADS, PlanCache
from repro.vx.policy import (BANK_FIELDS, BANK_STRIDES, IMPLS,
                             MIN_FUSED_ELEMS, Policy, current, resolve, use)
from repro.vx.program import Program, Shard, Txn
from repro.vx.spec import (BANK, AccessSpec, Compact, Indexed, Paged,
                           Segment, Strided)

__all__ = [
    "AccessSpec", "Strided", "Segment", "Indexed", "Compact", "Paged",
    "BANK",
    "gather", "scatter", "transpose", "compact", "gather_many",
    "scatter_many", "warm",
    "Policy", "use", "current", "resolve",
    "PLANS", "SEGMENT_LOADS", "PlanCache",
    "Shard", "Program", "Txn", "program", "lower",
    "MIN_FUSED_ELEMS", "BANK_STRIDES", "BANK_FIELDS", "IMPLS",
]
