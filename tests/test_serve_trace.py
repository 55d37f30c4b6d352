"""The serve path's spans, counters and program names: ``serve.*`` host
spans inside ``Scheduler.tick`` under the profiler, the ``host_syncs``
count against the reads a tick makes, and the jitted programs' names."""
import functools
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_arch
from repro.models.transformer import init_params
from repro.serve.paged_cache import PagedCache
from repro.serve.scheduler import Scheduler


@functools.lru_cache(maxsize=None)
def _cfg_params():
    cfg = get_arch("qwen3-0.6b").smoke
    return cfg, init_params(cfg, jax.random.key(0))


def _sched():
    cfg, params = _cfg_params()
    return Scheduler(cfg, params, slots=2, max_len=64, page_size=16)


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; returns its ``serve.*`` host events
    as (name, start, end, stats), the stats as a dict."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats) if e.name == "serve.prefill_chunk" else {})
            for p in ProfileData.from_file(path).planes
            for line in p.lines for e in line.events
            if e.name.startswith("serve.")]


def _inside(ev, span):
    return span[1] <= ev[1] and ev[2] <= span[2]


def test_serve_spans_nest_under_the_tick(tmp_path):
    s = _sched()
    req = s.submit(list(range(1, 21)), max_new_tokens=3)
    s.tick()                      # warm: compiles the chunk, no decode yet
    serve = _traced(tmp_path, lambda: (s.tick(), s.tick()))
    ticks = [e for e in serve if e[0] == "serve.tick"]
    assert len(ticks) == 2
    for e in serve:
        assert any(_inside(e, t) for t in ticks), e[0]
    names = {e[0] for e in serve}
    assert {"serve.admit", "serve.prefill_chunk", "serve.page_guard",
            "serve.decode", "serve.decode.dispatch",
            "serve.decode.readback", "serve.retire",
            "serve.host_sync"} <= names
    (chunk,) = [e for e in serve if e[0] == "serve.prefill_chunk"]
    assert chunk[3]["rid"] == req.rid and chunk[3]["slot"] == req.slot
    decode = [e for e in serve if e[0] == "serve.decode"]
    for child in ("serve.decode.dispatch", "serve.decode.readback"):
        assert all(any(_inside(e, d) for d in decode)
                   for e in serve if e[0] == child)


def test_host_syncs_count_the_reads_a_tick_makes(monkeypatch):
    """A 20-token prompt (two 16-token chunks at one chunk a tick): the
    first tick reads the free count twice for its chunk and twice in the
    page guard; the second as much, plus the decode step's sampled
    tokens.  The reads themselves are counted too, where the device
    value reaches the host: the pool's free count, and ``np.asarray`` of
    a device array."""
    s = _sched()
    s.submit(list(range(1, 21)), max_new_tokens=3)
    reads = [0]
    free_pages, asarray = PagedCache.free_pages, np.asarray

    def counted_free(cache):
        reads[0] += 1
        return free_pages(cache)

    def counted_asarray(a, *args, **kw):
        reads[0] += isinstance(a, jax.Array)
        return asarray(a, *args, **kw)
    monkeypatch.setattr(PagedCache, "free_pages", counted_free)
    monkeypatch.setattr(np, "asarray", counted_asarray)
    counts, witnessed = [], []
    for _ in range(2):
        n, r = s.host_syncs, reads[0]
        s.tick()
        counts.append(s.host_syncs - n)
        witnessed.append(reads[0] - r)
    assert counts == [4, 5] == witnessed
    st = s.stats()
    assert (st["decode_steps"], st["host_syncs"]) == (1, 9)


def test_programs_have_stable_names():
    s = _sched()
    toks = jnp.zeros((s.cache.page_size,), jnp.int32)
    chunk = s._chunk.lower(s.params, s.cache.state, toks, jnp.int32(0),
                           jnp.int32(1))
    logits = jnp.zeros((2, s.cfg.vocab), jnp.float32)
    modules = {
        "jit_decode_step": s.compile_decode(),
        "jit_split_keys": s._split_keys.lower(s._keys),
        "jit_prefill_chunk": chunk,
        "jit_sample": s._sample.lower(logits, s._keys),
        "jit_sample_checked": s._sample_guarded.lower(logits, s._keys),
        "jit_release_slot": s.cache._release.lower(s.cache.state,
                                                   jnp.int32(0)),
    }
    for name, stage in modules.items():
        if isinstance(stage, jax.stages.Lowered):
            stage = stage.compile()
        assert stage.as_text().startswith(f"HloModule {name},"), name


@pytest.mark.parametrize("guard_nan", [False, True])
def test_the_decode_readback_counts_each_read(guard_nan):
    s = Scheduler(*_cfg_params(), slots=2, max_len=64, page_size=16,
                  guard_nan=guard_nan)
    s.add_request(5)
    n = s.host_syncs
    s.step()
    assert s.host_syncs - n == (2 if guard_nan else 1)
