"""The seeded client: drives ``Scheduler.tick`` through the server's own
surface and keeps the log every metric is computed from.

Each pass of the loop submits the requests that are due, runs one tick,
and reads every live request's new tokens, stamping them with the host
clock at the tick's end (a tick ends in the host read of its sampled
tokens, so a stamped token exists on the host).  Arrivals never wait for
the server: a request is due at its drawn time and its latency counts from
then.  When nothing is queued or running the client sleeps until the next
arrival.  The window closes at the end of the first tick (or sleep) that
reaches ``seconds``.

With ``spans`` the client wraps its calls in profiler annotations
(``bench.submit``, ``bench.tick``, ``bench.readout``, ``bench.wait``, and
``bench.window`` around the whole window), which a device trace puts on
the same clock as the device's operations.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque

import numpy as np

#: JAX's monitoring event for one backend (XLA) compilation.
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class ReqLog:
    idx: int
    due: float                      # absolute host time
    prompt: np.ndarray
    max_new: int
    slot_t: float | None = None     # start of the first tick that holds it
    tok_t: list = dataclasses.field(default_factory=list)
    tokens: list | None = None      # served tokens, once finished
    state: str = "unsent"
    handle: object = None
    prefilled: int = 0
    generated: int = 0


@dataclasses.dataclass
class TickLog:
    start: float
    end: float
    prefill_tokens: int = 0
    decode_tokens: int = 0
    attn_pairs: int = 0             # sum over processed tokens of keys read
    kv_ctx_tokens: int = 0          # live context read, once per sequence
    pages_in_use: int | None = None


@dataclasses.dataclass
class Log:
    t_open: float
    t_close: float
    reqs: list
    ticks: list
    num_pages: int
    compiles_in_window: int = 0

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


def _progress(sched, r: ReqLog) -> tuple[int, int]:
    """(prompt tokens prefilled, tokens generated) of a request now."""
    h, P = r.handle, len(r.prompt)
    if h.state.value == "finished":
        return P - 1, len(h.tokens) - P
    if h.slot is None:
        return r.prefilled, r.generated
    s = h.slot
    pre = sched._prefilling.get(s, P - 1)
    return pre, max(len(sched.tokens[s]) - P, 0)


class Client:
    def __init__(self, server, reqs, seconds: float, *, spans: bool = False,
                 pool: bool = False, clock=time.perf_counter,
                 sleep=time.sleep):
        self.server, self.sched = server, server.scheduler
        self.reqs, self.seconds = reqs, seconds
        self.spans, self.pool = spans, pool
        self.clock, self.sleep = clock, sleep

    def _span(self, name):
        if not self.spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def run(self) -> Log:
        from jax import monitoring
        compiles = [0]

        def on_event(event, *a, **k):
            if event == BACKEND_COMPILE:
                compiles[0] += 1

        sched, clock = self.sched, self.clock
        t_open = clock()
        logs = [ReqLog(q.idx, t_open + q.due, q.prompt, q.max_new)
                for q in self.reqs]
        pending = deque(sorted(logs, key=lambda r: r.due))
        live: list[ReqLog] = []
        ticks: list[TickLog] = []
        end = t_open + self.seconds
        monitoring.register_event_duration_secs_listener(on_event)
        with self._span("bench.window"):
            while True:
                now = clock()
                if now >= end:
                    break
                if pending and pending[0].due <= now:
                    with self._span("bench.submit"):
                        while pending and pending[0].due <= now:
                            r = pending.popleft()
                            r.handle = self.server.submit(
                                r.prompt.tolist(), max_new_tokens=r.max_new)
                            r.state = "queued"
                            live.append(r)
                if sched.drained():
                    wake = min(pending[0].due if pending else end, end)
                    with self._span("bench.wait"):
                        self.sleep(max(wake - clock(), 0.0))
                    continue
                t0 = clock()
                with self._span("bench.tick"):
                    self.server.tick()
                t1 = clock()
                with self._span("bench.readout"):
                    live = self._readout(live, TickLog(t0, t1), ticks)
        t_close = clock()
        monitoring.unregister_event_duration_listener(on_event)
        return Log(t_open, t_close, logs, ticks, sched.cache.num_pages,
                   compiles[0])

    def _readout(self, live, tick: TickLog, ticks) -> list:
        sched, keep = self.sched, []
        for r in live:
            pre, gen = _progress(sched, r)
            if r.slot_t is None and r.handle.state.value != "queued":
                r.slot_t = tick.start
            dp, dg = pre - r.prefilled, gen - r.generated
            if dp or dg:
                P = len(r.prompt)
                ctx0 = r.prefilled if r.generated == 0 else P - 1 + r.generated
                tick.kv_ctx_tokens += ctx0
                tick.prefill_tokens += dp
                tick.decode_tokens += dg
                # a token at position t attends t + 1 keys
                lo, hi = r.prefilled, pre
                tick.attn_pairs += (hi * (hi + 1) - lo * (lo + 1)) // 2
                lo, hi = P - 1 + r.generated, P - 1 + gen
                tick.attn_pairs += (hi * (hi + 1) - lo * (lo + 1)) // 2
                r.tok_t.extend([tick.end] * dg)
                r.prefilled, r.generated = pre, gen
            r.state = r.handle.state.value
            if r.handle.terminal:
                if r.state == "finished":
                    r.tokens = list(r.handle.tokens[len(r.prompt):])
            else:
                keep.append(r)
        if self.pool:
            tick.pages_in_use = sched.cache.pages_in_use()
        ticks.append(tick)
        return keep
