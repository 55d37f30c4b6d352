"""vx.Policy — the single knob stack for vector-access lowering.

PRs 1-2 grew three uncoordinated ways to choose a lowering: per-call
``impl=`` strings threaded through every layer, ``core/drom.default_impl``'s
platform probe, and the scheduler's module-level fusion/platform constants.
This module replaces all of them with one frozen :class:`Policy` resolved in
priority order:

1. an explicit ``policy=`` argument on a verb (a Policy, or an impl string
   as shorthand),
2. the innermost ``with vx.use(...)`` context (thread-local, nestable,
   exception-safe),
3. :meth:`Policy.default` — the ``REPRO_VX_IMPL`` environment variable,
   else the platform default (``pallas`` on TPU, ``ref`` elsewhere).

Everything tunable about dispatch lives on the Policy: the impl family,
the scheduler's fusion threshold (below which a merged group rides the XLA
path instead of paying a kernel launch), the runtime-stride bank contents,
and whether the platform lowering rule (off-TPU merged groups lower to
XLA) applies.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading

#: Below this many elements a merged group is inlined on the XLA path
#: instead of paying a kernel launch (decode-time single-token beats).
MIN_FUSED_ELEMS = 1 << 15

#: What the runtime-stride plan bank precompiles: strides +-1..8 (the
#: negative half via the Reverser) and the segment field counts occurring
#: in this repo's models/data paths.
BANK_STRIDES = tuple(range(1, 9))
BANK_FIELDS = (2, 4)

IMPLS = ("ref", "pallas", "pallas_dynamic")

ENV_VAR = "REPRO_VX_IMPL"


def _platform() -> str:
    import jax

    return jax.devices()[0].platform


@dataclasses.dataclass(frozen=True)
class Policy:
    """How vx verbs lower.  Frozen and hashable (usable in cache keys)."""

    impl: str = "ref"                       # ref | pallas | pallas_dynamic
    fusion_threshold: int = MIN_FUSED_ELEMS
    bank_strides: tuple = BANK_STRIDES
    platform_lowering: bool = True          # off-TPU merged groups -> XLA

    def __post_init__(self):
        if self.impl not in IMPLS:
            raise ValueError(
                f"unknown impl {self.impl!r} (want one of {IMPLS})")
        object.__setattr__(self, "bank_strides", tuple(self.bank_strides))

    @staticmethod
    def default() -> "Policy":
        """Process-wide default: ``REPRO_VX_IMPL`` env var, else platform
        (``pallas`` on TPU, ``ref`` elsewhere).  This is the ONE resolution
        point — ``core/drom.default_impl`` and ``ModelConfig.kernel_impl``
        both route here, so one knob controls the whole stack."""
        return _default_policy(os.environ.get(ENV_VAR), _platform())

    def with_impl(self, impl: str | None) -> "Policy":
        if impl is None or impl == self.impl:
            return self
        return dataclasses.replace(self, impl=impl)

    def for_elems(self, total_elems: int) -> "Policy":
        """Scheduler launch policy: accesses below the fusion threshold
        ride the XLA path (a scheduler does not issue a wide transaction
        for one beat)."""
        if self.impl == "ref" or total_elems >= self.fusion_threshold:
            return self
        return dataclasses.replace(self, impl="ref")


@functools.lru_cache(maxsize=None)
def _default_policy(env_impl: str | None, platform: str) -> Policy:
    impl = env_impl or ("pallas" if platform == "tpu" else "ref")
    return Policy(impl=impl)


_tls = threading.local()


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def current() -> Policy:
    """The active policy: innermost ``vx.use`` scope, else the default.

    NOTE: verbs read this at TRACE time.  A function already traced by
    ``jax.jit`` keeps the lowering it was traced with — changing the
    ambient policy (or ``REPRO_VX_IMPL``) later does not re-trace it.
    Pin ``policy=`` explicitly (or re-jit) when a call site must follow a
    policy that changes within the process."""
    s = _stack()
    return s[-1] if s else Policy.default()


def resolve(policy: "Policy | str | None" = None) -> Policy:
    """Normalize a verb's ``policy=`` argument.

    ``None`` -> the active policy; an impl string -> the active policy with
    that impl (shorthand easing migration from ``impl=`` call sites); a
    :class:`Policy` -> itself."""
    if policy is None:
        return current()
    if isinstance(policy, str):
        return current().with_impl(policy)
    if isinstance(policy, Policy):
        return policy
    raise TypeError(f"policy must be Policy | str | None, got {policy!r}")


@contextlib.contextmanager
def use(policy: "Policy | str | None" = None, **overrides):
    """Scope a policy: ``with vx.use("pallas"): ...`` or
    ``with vx.use(fusion_threshold=0): ...``.  Nests; the previous policy
    is restored on exit (including on exceptions)."""
    base = resolve(policy)
    pol = dataclasses.replace(base, **overrides) if overrides else base
    s = _stack()
    s.append(pol)
    try:
        yield pol
    finally:
        s.pop()
