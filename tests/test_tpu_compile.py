"""The served path's Pallas kernels compile for a TPU v5e at real width.

Nothing runs: each test compiles for a described (not attached) v5e chip
and checks that the compiled program launches a Mosaic kernel
(``tpu_custom_call``) — what interpret-mode CPU runs cannot show.  The
topology is described inside a fixture, never at import: only one process
may hold the TPU library, and every test worker imports this file.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro import vx
from repro.configs import get_arch
from repro.kernels import _common, kv_interleaved, segment
from repro.models import decode as dec
from repro.models import layers
from repro.models.transformer import init_params
from repro.serve.scheduler import Scheduler

ROWS, N = 4096, 256          # head_dim 128, K|V interleaved
SLOTS, MAX_LEN, PAGE_SIZE = 8, 2048, 16   # chip_smoke.py's serve geometry


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_tpu(monkeypatch):
    """Kernels lowered for the chip, not the interpreter, and no
    persistent cache: a described chip's executables cannot be read
    back here."""
    monkeypatch.setattr(_common, "interpret_mode", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _kernels(fn, *shapes) -> int:
    return jax.jit(fn).lower(*shapes).compile().as_text().count(
        "tpu_custom_call")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_segment_deinterleave_compiles(for_tpu, one_chip, dtype):
    aos = jax.ShapeDtypeStruct((ROWS, N), dtype, sharding=one_chip)
    assert _kernels(lambda a: segment.deinterleave(a, 2), aos) >= 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_segment_interleave_compiles(for_tpu, one_chip, dtype):
    half = jax.ShapeDtypeStruct((ROWS, N // 2), dtype, sharding=one_chip)
    assert _kernels(lambda k, v: segment.interleave([k, v]),
                    half, half) >= 1


def test_glu_split_compiles_through_the_shift_plans(for_tpu, one_chip):
    """The decode step's SwiGLU gate/up split at qwen3-0.6b width: 8 rows,
    not a whole 128-row chunk, so the shift plans route it."""
    vx.SEGMENT_LOADS.clear()
    gu = jax.ShapeDtypeStruct((SLOTS, 6144), jnp.float32, sharding=one_chip)
    assert _kernels(lambda a: segment.deinterleave(a, 2), gu) >= 1
    (route,) = vx.SEGMENT_LOADS.stats()
    assert route in ("fused", "per_field")


@pytest.mark.parametrize("n,fields,dtype", [
    (256, 2, jnp.float32), (256, 2, jnp.int32), (128, 2, jnp.float32),
    (512, 2, jnp.float32), (384, 3, jnp.float32), (512, 4, jnp.float32),
    (1024, 8, jnp.float32), (128, 16, jnp.float32), (2048, 2, jnp.float32),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_transpose_route_compiles(for_tpu, one_chip, n, fields, dtype):
    """Each kind of (n, fields) the transpose route accepts is one Mosaic
    kernel for the chip: the transposes and sublane-strided reads lower."""
    vx.SEGMENT_LOADS.clear()
    aos = jax.ShapeDtypeStruct((ROWS, n), dtype, sharding=one_chip)
    assert _kernels(lambda a: segment.deinterleave(a, fields), aos) == 1
    assert vx.SEGMENT_LOADS.stats() == {"transpose": 1}


def test_paged_kv_split_compiles_for_qwen3_pool(for_tpu, one_chip):
    """The decode step's fused FIELD=2 split over every layer of the
    gathered qwen3-0.6b float32 pool, through the transpose route."""
    cfg = get_arch("qwen3-0.6b").model
    state = jax.eval_shape(lambda: dec.init_paged_cache(
        cfg, SLOTS, MAX_LEN, PAGE_SIZE, jnp.float32))
    vx.SEGMENT_LOADS.clear()
    with vx.use("pallas"):
        (gathered,) = jax.eval_shape(
            lambda p, t: kv_interleaved.gather_paged_kv([p], t, PAGE_SIZE),
            state["blocks"]["pos0"], state["table"])
        n = _kernels(lambda g: kv_interleaved.split_kv_step([g]),
                     jax.ShapeDtypeStruct(gathered.shape, gathered.dtype,
                                          sharding=one_chip))
    assert n >= 1
    assert vx.SEGMENT_LOADS.stats() == {"transpose": 1}


def _serve_programs(cfg, one_chip):
    """The scheduler's decode step and prefill chunk compiled for the chip
    under the kernel lowering: ``(text, routes)`` of each, ``routes`` the
    segment loads its trace lowered (``vx.SEGMENT_LOADS``)."""
    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def compiled(lower):
        vx.SEGMENT_LOADS.clear()
        text = lower().compile().as_text()
        return text, vx.SEGMENT_LOADS.stats()
    params = on_chip(jax.eval_shape(lambda: init_params(
        cfg, jax.random.key(0))))
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    with vx.use("pallas"):
        sched = Scheduler(cfg, params, slots=SLOTS, max_len=128,
                          page_size=PAGE_SIZE)
        state = on_chip(sched.cache.state)
        step = compiled(lambda: sched._step.lower(
            params, state,
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((SLOTS,), bool, sharding=one_chip)))
        chunk = compiled(lambda: sched._chunk.lower(
            params, state,
            jax.ShapeDtypeStruct((PAGE_SIZE,), jnp.int32, sharding=one_chip),
            i32, i32))
    return step, chunk


def _pool_moves(text: str, shapes) -> list[str]:
    """Instructions of compiled ``text`` that copy, slice or update a
    whole array of one of ``shapes`` (``f32[...]`` strings): a result of
    that shape whose opcode or name says copy, dynamic-slice or
    dynamic-update-slice (device traces name fusions after the latter)."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = ([a-z0-9]+\[[\d,]*\])\S* "
                     r"([\w-]+)\(", line)
        if not m or m.group(2) not in shapes:
            continue
        name = m.group(1).replace("_", "-")
        if any(op == m.group(3) or op in name
               for op in ("copy", "dynamic-slice", "dynamic-update-slice")):
            found.append(line.strip()[:160])
    return found


def _aliased_params(text: str) -> set[int]:
    """Entry parameters the compiled module aliases to an output."""
    head = text.split("\n", 1)[0]
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", head)
    return ({int(p) for p in re.findall(r"\((\d+), \{", alias.group(1))}
            if alias else set())


def _param_index(text: str, shape: str) -> int:
    """Index of the first entry parameter of ``shape``."""
    layout = re.search(r"entry_computation_layout=\{\((.*?)\)->",
                       text.split("\n", 1)[0]).group(1)
    return re.findall(r"[a-z0-9]+\[[\d,]*\]", layout).index(shape)


def _expert_cell_attention():
    """The expert cell's attention and pool geometry (d 2048, 32/4 heads of
    128, a float32 pool of 4 KV heads, bfloat16 weights) over 8 of its
    layers, with a narrow dense FFN: at this pool layout a write whose
    window spans the layer axis makes XLA lay the whole pool out twice
    around it."""
    return dataclasses.replace(get_arch("qwen3-0.6b").model, d_model=2048,
                               n_layers=8, n_heads=32, n_kv_heads=4,
                               d_ff=64, param_dtype="bfloat16")


@pytest.mark.parametrize("make_cfg,slots", [
    (lambda: get_arch("qwen3-0.6b").model, SLOTS),
    (_expert_cell_attention, 16),
], ids=["qwen3-0.6b", "expert-cell-attention"])
def test_serve_programs_write_the_pool_in_place(for_tpu, one_chip,
                                                 monkeypatch, make_cfg,
                                                 slots):
    """At qwen3-0.6b's published widths and chip_smoke.py's serve geometry
    (8 slots, ``max_len`` 2048, 16-token pages, a 3.76 GB float32 pool),
    and at the expert cell's pool geometry (16 slots), the scheduler's
    decode step and prefill chunk compile for the chip with no copy,
    dynamic-slice or dynamic-update-slice of the pool stack, of one
    layer's pool or of the stack flattened to one page axis; the donated
    pool parameter aliases an output; and beside what each call holds
    anyway, XLA's temporaries are smaller than one layer's pool.  What
    each call holds anyway: the bfloat16 copy of float32 layer weights
    (``cast_params``; none for bfloat16 weights) in the chunk, and the
    whole-row page gather with its K/V split in the step."""
    cfg = make_cfg()
    ns = cfg.n_superblocks
    # the scheduler's cache as shapes: nothing pool-sized is allocated
    init = dec.init_paged_cache
    monkeypatch.setattr(dec, "init_paged_cache",
                        lambda *a, **k: jax.eval_shape(
                            lambda: init(*a, **k)))
    abstract = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), abstract)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    with vx.use("pallas"):
        sched = Scheduler(cfg, params, slots=slots, max_len=MAX_LEN,
                          page_size=PAGE_SIZE)
        state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), sched.cache.state)
        step = sched._step.lower(
            params, state,
            jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((slots,), bool, sharding=one_chip)
        ).compile()
        chunk = sched._chunk.lower(
            params, state,
            jax.ShapeDtypeStruct((PAGE_SIZE,), jnp.int32, sharding=one_chip),
            i32, i32).compile()
    pool = state["blocks"]["pos0"].shape
    layer_bytes = math.prod(pool[1:]) * 4    # 134 MB for qwen3-0.6b
    shapes = {"f32[" + ",".join(map(str, s)) + "]"
              for s in (pool, pool[1:], (pool[0] * pool[1],) + pool[2:])}
    stack = "f32[" + ",".join(map(str, pool)) + "]"
    weights = sum(a.size * cfg.cdtype.itemsize
                  for a in jax.tree.leaves(abstract["blocks"])
                  if a.dtype != cfg.cdtype)
    rows = ns * slots * MAX_LEN * math.prod(pool[3:]) * 4
    for compiled, held in ((chunk, weights), (step, 2 * rows)):
        text = compiled.as_text()
        assert _pool_moves(text, shapes) == []
        assert _param_index(text, stack) in _aliased_params(text)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp - held < layer_bytes, (temp, held)


def _assert_one_named_split(step: str, chunk: str) -> None:
    def split_names(text):
        return [line.split(" = ")[0].strip() for line in text.splitlines()
                if line.strip().startswith(("%kv_split", "ROOT %kv_split"))]
    assert step.startswith("HloModule jit_decode_step,")
    (name,) = split_names(step)
    (call,) = [line for line in step.splitlines()
               if line.strip().startswith(name + " = ")]
    assert 'custom_call_target="tpu_custom_call"' in call
    assert chunk.startswith("HloModule jit_prefill_chunk,")
    assert split_names(chunk) == []


def test_decode_step_and_its_split_carry_stable_names(for_tpu, one_chip):
    """The scheduler's decode step compiles as the module
    ``jit_decode_step``, and its whole-step FIELD=2 split is its one
    instruction named ``kv_split``, a Mosaic call: the names a device
    trace's ``XLA Modules`` and ``XLA Ops`` events carry (smoke widths:
    the names do not depend on them).  The prefill chunk has no
    ``kv_split`` instruction, so the device time of every ``kv_split``
    op is the decode steps' split alone."""
    (step, _), (chunk, _) = _serve_programs(get_arch("qwen3-0.6b").smoke,
                                            one_chip)
    _assert_one_named_split(step, chunk)


def test_serve_programs_split_kv_through_the_transpose_route(for_tpu,
                                                            one_chip):
    """At head_dim 128 (qwen3-0.6b's; two layers of smoke width
    otherwise) the decode step's whole-step split and the prefill chunk's
    row split (traced once, in its layer loop) take the transpose route,
    while the chunk's 16-row splits, SwiGLU gate/up and the fresh K|V
    beats, keep the shift plans (the step's ride the XLA path).  The
    split keeps its name: one ``kv_split`` Mosaic call in the step."""
    cfg = dataclasses.replace(get_arch("qwen3-0.6b").smoke, head_dim=128)
    (step, step_routes), (chunk, chunk_routes) = _serve_programs(cfg,
                                                                 one_chip)
    assert step_routes == {"transpose": 1}
    assert chunk_routes.pop("transpose") == 1
    assert set(chunk_routes) <= {"fused", "per_field"} and chunk_routes
    _assert_one_named_split(step, chunk)


def test_expert_layer_products_carry_stable_names(for_tpu, one_chip):
    """A held-range expert model's decode step and prefill chunk run its
    grouped products as the Mosaic calls ``lax.ragged_dot`` lowers to,
    named ``ragged-dot-<mode>`` (and their group metadata
    ``ragged-dot-metadata``): the names a device trace's ``XLA Ops``
    events carry.  The KV split keeps its one named call in the step."""
    (step, _), (chunk, _) = _serve_programs(
        get_arch("qwen3-moe-30b-a3b").smoke, one_chip)
    _assert_one_named_split(step, chunk)
    for text in (step, chunk):
        calls = [line for line in text.splitlines()
                 if line.strip().startswith("%ragged-dot-")]
        names = {c.split(" = ")[0].strip().split(".")[0] for c in calls}
        assert names == {"%ragged-dot-metadata", "%ragged-dot-none"}
        assert all('custom_call_target="tpu_custom_call"' in c
                   for c in calls)


@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_qk_norm_sums_in_fixed_order(for_tpu, one_chip, impl):
    """One prefill chunk's K|V beats at qwen3-0.6b width (split, k-norm,
    RoPE, re-interleave): under either lowering the norm's sum of squares
    compiles to elementwise folds, not a reduce whose order follows the
    layout the lowering gives K."""
    seg = vx.Segment(n=N, fields=2)

    def beats(kv, scale):
        k, v = vx.transpose(seg, kv, policy=impl)
        k = layers.rope(layers.head_rms_norm(k, scale),
                        jnp.arange(PAGE_SIZE)[None], 1e6)
        return vx.transpose(seg, [k, v], policy=impl)

    kv = jax.ShapeDtypeStruct((1, PAGE_SIZE, 8, N), jnp.bfloat16,
                              sharding=one_chip)
    scale = jax.ShapeDtypeStruct((N // 2,), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(beats).lower(kv, scale).compile().as_text()
    assert " reduce(" not in text
    assert ("tpu_custom_call" in text) == (impl == "pallas")
