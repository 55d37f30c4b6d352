"""The segment load's transpose route (kernels/segment.py), bit for bit.

A FIELD=f load of 32-bit words in whole 128-row chunks is transposed into
VMEM and read back with a sublane stride; every other shape keeps the
shift plans.  Both are pure permutations, so every output word must equal
the oracle's: NaN payloads, infinities and -0.0 included.  The route each
load took is read from ``vx.SEGMENT_LOADS``.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import vx
from repro.core import accessfuse
from repro.kernels import kv_interleaved, ref, segment

# NaNs (quiet, signalling, with payloads, negative), infinities, zeros.
SPECIAL = np.array([0x7FC00000, 0xFFC00000, 0x7FC00001, 0x7F800001,
                    0xFFBADBAD, 0x7F800000, 0xFF800000, 0x80000000,
                    0x00000000], np.uint32)


def words(shape, seed: int) -> np.ndarray:
    """Random 32-bit words with every SPECIAL pattern sprinkled in."""
    rng = np.random.default_rng(seed)
    w = rng.integers(0, 2 ** 32, size=shape, dtype=np.uint32)
    flat = w.reshape(-1)
    at = rng.choice(flat.size, size=4 * SPECIAL.size, replace=False)
    flat[at] = np.tile(SPECIAL, 4)
    return w


def bits(x) -> np.ndarray:
    return np.asarray(x).view(np.uint32)


def assert_split_exact(aos, outs, fields):
    want = ref.deinterleave(aos, fields)
    assert len(outs) == fields
    for f, (o, w) in enumerate(zip(outs, want)):
        np.testing.assert_array_equal(bits(o), bits(w))
        np.testing.assert_array_equal(bits(o), bits(aos[..., f::fields]))


@pytest.fixture
def loads():
    vx.SEGMENT_LOADS.clear()
    yield vx.SEGMENT_LOADS
    vx.SEGMENT_LOADS.clear()


# (shape, fields): qwen3-0.6b's per-layer prefill row (2048 tokens x 8 KV
# heads, K|V of head_dim 128), rows over several blocks, and the (n,
# fields) pairs the route accepts at both ends of the field width.
ROUTED = [((16384, 256), 2), ((2, 4, 128, 8, 256), 2), ((640, 256), 2),
          ((256, 128), 2), ((384, 384), 3), ((256, 512), 4),
          ((128, 1024), 8), ((128, 128), 16)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32],
                         ids=["float32", "int32"])
@pytest.mark.parametrize("shape,fields", ROUTED,
                         ids=[f"{'x'.join(map(str, s))}-f{f}"
                              for s, f in ROUTED])
def test_transpose_route_is_exact(loads, shape, fields, dtype):
    aos = jnp.asarray(words(shape, 14).view(dtype))
    outs = segment.deinterleave(aos, fields)
    assert loads.stats() == {"transpose": 1}
    assert_split_exact(aos, outs, fields)


@pytest.mark.parametrize("rows,n,fields,block", [
    (3670016, 256, 2, 1024),     # qwen3-0.6b's decode-step pool split
    (16384, 256, 2, 1024),       # its per-layer prefill row
    (640, 256, 2, 128),          # 5 chunks: the height divides the rows
    (4096, 512, 2, 512),         # wider rows, same block bytes
    (4096, 2048, 2, 128),
])
def test_block_height_fits_rows_and_bytes(rows, n, fields, block):
    assert segment.transpose_block_rows(rows, n, fields,
                                        jnp.float32) == block
    assert rows % block == 0


@pytest.mark.parametrize("shape,dtype", [
    ((8, 6144), jnp.float32),    # the decode step's GLU gate/up split
    ((64, 6144), jnp.float32),   # the prefill chunk's
    ((256, 6144), jnp.float32),  # whole chunks, but over the VMEM budget
    ((256, 256), jnp.bfloat16),  # 16-bit words
    ((100, 256), jnp.float32),   # rows not in whole 128-row chunks
    ((128, 192), jnp.float32),   # n not in whole 128-lane vregs
], ids=["glu-decode", "glu-prefill", "glu-wide", "bfloat16", "rows-100",
        "n-192"])
def test_other_shapes_keep_the_shift_plans(loads, shape, dtype):
    rows = int(np.prod(shape[:-1]))
    assert segment.transpose_block_rows(rows, shape[-1], 2, dtype) == 0
    aos = jnp.asarray(words(shape, 15).view(np.float32)).astype(dtype)
    outs = segment.deinterleave(aos, 2)
    (route,) = loads.stats()
    assert route in ("fused", "per_field")
    assert_split_exact(aos, outs, 2)


def test_dynamic_oracle_agrees_with_transpose_route(loads):
    aos = jnp.asarray(words((1024, 256), 16).view(np.float32))
    fast = segment.deinterleave(aos, 2)
    slow = segment.deinterleave(aos, 2, fused=False)
    assert loads.stats() == {"transpose": 1, "dynamic": 1}
    for a, b in zip(fast, slow):
        np.testing.assert_array_equal(bits(a), bits(b))


def test_whole_step_kv_split_takes_the_transpose_route(loads):
    """``split_kv_step`` as the decode step calls it: same-shape layers
    stacked into one launch, at the kernel lowering a TPU picks."""
    kvs = [jnp.asarray(words((2, 64, 8, 256), 17 + i).view(np.float32))
           for i in range(3)]
    with accessfuse.pinned_kernel_lowering(), vx.use("pallas"):
        pairs = kv_interleaved.split_kv_step(kvs)
    assert loads.stats() == {"transpose": 1}
    for kv, pair in zip(kvs, pairs):
        assert_split_exact(kv, pair, 2)
