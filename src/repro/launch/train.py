"""Training CLI: end-to-end driver over the full substrate.

Runs any ``--arch`` (full or smoke geometry) with the synthetic AoS data
pipeline, AdamW, checkpointing (async, atomic), straggler policy hooks and
optional gradient compression / microbatching.

Recommended XLA flags on real TPU fleets (overlap compute/collectives):
  --xla_tpu_enable_latency_hiding_scheduler=true
  --xla_tpu_enable_async_collective_fusion=true
  --xla_tpu_overlap_compute_collective_tc=true

Example (CPU, reduced geometry):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 20 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.data.pipeline import DataConfig, SyntheticAoSPipeline
from repro.dist.sharding import local_ctx
from repro.ft.checkpoint import CheckpointManager
from repro.ft.straggler import StragglerPolicy
from repro.launch import compile_cache
from repro.launch.mesh import make_ctx
from repro.optim.adamw import AdamWConfig
from repro.optim.compression import CompressionConfig
from repro.train.step import (TrainConfig, init_full_state, jit_train_step)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family geometry (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default="none",
                    choices=["none", "single-pod", "multi-pod"],
                    help="production meshes need 256/512 devices (dry-run)")
    args = ap.parse_args()
    compile_cache.enable()

    arch = get_arch(args.arch)
    cfg = arch.smoke if args.smoke else arch.model
    if args.mesh == "none":
        ctx = local_ctx()
    else:
        from repro.launch.mesh import make_production_mesh
        ctx = make_ctx(make_production_mesh(
            multi_pod=args.mesh == "multi-pod"))

    tcfg = TrainConfig(
        optimizer=AdamWConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=max(1, args.steps // 10)),
        microbatches=args.microbatches,
        compression=CompressionConfig(kind=args.compression))

    pipe = SyntheticAoSPipeline(
        DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                   global_batch=args.batch),
        process_index=jax.process_index(),
        process_count=jax.process_count())
    straggler = StragglerPolicy(n_hosts=jax.process_count())
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    state = init_full_state(cfg, tcfg, jax.random.key(0))
    start_step = 0
    if mgr and args.resume and mgr.latest_step() is not None:
        state, extra = mgr.restore(state)
        pipe.load_state_dict(extra["pipeline"])
        start_step = extra["step"]
        print(f"resumed from step {start_step}")

    batch0 = pipe.next_batch()
    step_fn = jit_train_step(cfg, tcfg, ctx, state, batch0)
    pipe.load_state_dict({"step": pipe.state.step - 1,
                          "seed": pipe.state.seed})  # rewind the peek

    for step in range(start_step, args.steps):
        t0 = time.time()
        batch = pipe.next_batch()
        state, metrics = step_fn(state, batch)
        dt = time.time() - t0
        straggler.record_step({jax.process_index(): dt})
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} {dt*1e3:.0f}ms "
                  f"excluded_hosts={sorted(straggler.excluded())}",
                  flush=True)
        if mgr and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, state,
                     extra={"step": step + 1,
                            "pipeline": pipe.state_dict()})
    if mgr:
        mgr.save(args.steps, state,
                 extra={"step": args.steps, "pipeline": pipe.state_dict()},
                 blocking=True)
    print("done; final loss", float(metrics["loss"]))


if __name__ == "__main__":
    main()
