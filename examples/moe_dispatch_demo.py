"""EARTH MoE dispatch, visualized: routing -> compaction -> grouped GEMM.

Shows the shift-network token compaction (the paper's GSN with prefix-sum
SCG) packing each expert's tokens, and verifies against argsort dispatch.

Run:  PYTHONPATH=src python examples/moe_dispatch_demo.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scg, shiftnet
from repro.models.moe import MoESpec, init_moe, moe_ffn_local

T, d, E, k = 16, 32, 4, 2
key = jax.random.key(0)
params = init_moe(key, d, MoESpec(n_experts=E, top_k=k, d_ff=64),
                  jnp.float32)
x = jax.random.normal(jax.random.key(1), (T, d))

# --- routing ---------------------------------------------------------------
logits = x @ params["router"]
topw, topi = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
print("expert assignment per token (top-2):")
print(np.asarray(topi).T)

# --- EARTH compaction for expert 0 ----------------------------------------
units = topi.reshape(-1)
mine = units == 0
shift, valid = scg.compaction_counts(mine)
ids = jnp.arange(T * k, dtype=jnp.int32)
res = shiftnet.gather_network(ids, shift, valid)
n0 = int(mine.sum())
print(f"\nexpert 0 owns {n0} (token,slot) units; "
      f"compacted unit ids: {np.asarray(res.payload[:n0])}")
print("conflict-free routing:", not bool(res.conflict))

# the same compaction through the public vx API (what moe.py calls)
from repro import vx
packed = vx.compact(vx.Compact(n=T * k, cap=T * k), mine)
print("vx.compact agrees:",
      bool(jnp.all(packed[:n0] == res.payload[:n0])))

# --- full MoE layer: earth vs argsort dispatch ------------------------------
for dispatch in ("earth", "sort"):
    spec = MoESpec(n_experts=E, top_k=k, d_ff=64, dispatch=dispatch)
    y, aux, _ = moe_ffn_local(params["router"], params["wg"], params["wu"],
                              params["wo"], x, spec, model_axis=None,
                              data_axes=(), n_shards=1)
    print(f"{dispatch:6s}: |y|={float(jnp.linalg.norm(y)):.4f} "
          f"aux={float(aux):.4f}")
