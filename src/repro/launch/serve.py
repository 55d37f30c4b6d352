"""Serving CLI: paged continuous batching (prefill + decode + sampling)
through the hardened request lifecycle (typed requests, deadlines,
preemption-and-restore, runtime guards), an optional in-process replica
FLEET (least-loaded routing, health tracking, replay-based failover),
and chaos modes for both layers.

Clean serving exits NONZERO (3) unless every request ends FINISHED with
exactly ``--gen`` new tokens before the tick cap.

Example (CPU, reduced geometry):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --requests 4 --prompt-len 16 --gen 12 --page-size 16 \
      --temperature 0.8 --top-k 40

Published widths on one TPU v5e:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
      --requests 8 --prompt-len 512 --gen 32 --max-len 2048

Fleet failover smoke (3 replicas, kill one mid-decode, work migrates):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --replicas 3 --kill-replica 4 --requests 6 --gen 8

Chaos smoke (seeded fault plan, invariants audited every tick; with
--replicas > 1 the plan adds replica kills / hangs / admission storms
and the fleet residency audit).  Exits NONZERO when the audit trips or
any request ends non-typed — CI gates on the exit code:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --chaos 0 --requests 6 --gen 6 [--replicas 3]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.ft.straggler import StepWatchdog
from repro.launch import compile_cache
from repro.models.transformer import init_params
from repro.serve.engine import BatchedServer
from repro.serve.lifecycle import (LifecycleError, RequestState,
                                   TERMINAL_STATES)
from repro.serve.paged_cache import InvariantViolation

EXIT_CHAOS = 2          # audit tripped / non-typed termination / livelock
EXIT_UNFINISHED = 3     # clean serving: tick cap hit or a request not FINISHED


def _check_typed(requests) -> list[str]:
    """Every request must sit in a TERMINAL typed state, and FAILED ones
    must carry an error string — anything else is a lifecycle escape."""
    problems = []
    for r in requests:
        if r.state not in TERMINAL_STATES:
            problems.append(f"req {r.rid} non-terminal: {r.state.value}")
        elif r.state is RequestState.FAILED and not r.error:
            problems.append(f"req {r.rid} FAILED without a typed error")
    return problems


def _print_stats(stats: dict) -> None:
    """--stats: latency percentiles + speculation acceptance."""
    lat = stats.get("latency") or {}
    if lat:
        print(f"latency: ttft p50={lat.get('ttft_p50_s', 0.0):.4f}s "
              f"p99={lat.get('ttft_p99_s', 0.0):.4f}s; "
              f"inter-token p50={lat.get('itl_p50_s', 0.0):.5f}s "
              f"p99={lat.get('itl_p99_s', 0.0):.5f}s")
    else:
        print("latency: no samples recorded")
    sp = stats.get("speculative")
    if sp:
        print(f"speculative: K={sp.get('k', '?')} "
              f"acceptance={sp['acceptance']:.2f} "
              f"({sp['accepted']}/{sp['proposed']} drafts accepted)")


def _run_chaos_single(sched, args) -> int:
    from repro.serve.chaos import ChaosConfig, FaultPlan, run_plan
    plan = FaultPlan(ChaosConfig(seed=args.chaos, requests=args.requests,
                                 max_prompt=min(args.prompt_len,
                                                args.max_len // 2),
                                 max_new_tokens=args.gen))
    t0 = time.time()
    try:
        rep = run_plan(sched, plan)
    except (InvariantViolation, LifecycleError) as e:
        print(f"CHAOS FAIL: audit tripped: {type(e).__name__}: {e}")
        return EXIT_CHAOS
    dt = time.time() - t0
    print(f"chaos seed {args.chaos}: {rep.ticks} ticks in {dt:.2f}s — "
          f"states={rep.states} preemptions={rep.preemptions} "
          f"nan_failures={rep.nan_failures} "
          f"invariant_checks={rep.invariant_checks} "
          f"backpressured={rep.backpressured}")
    problems = _check_typed(rep.submitted)
    if problems:
        print("CHAOS FAIL: " + "; ".join(problems))
        return EXIT_CHAOS
    print("every request reached a terminal typed state; "
          "invariants never tripped")
    if args.stats:
        _print_stats(sched.stats())
    return 0


def _run_chaos_fleet(router, args) -> int:
    from repro.serve.chaos import (FleetChaosConfig, FleetFaultPlan,
                                   run_fleet_plan)
    from repro.serve.fleet import FleetAuditError
    plan = FleetFaultPlan(FleetChaosConfig(
        seed=args.chaos, replicas=args.replicas, requests=args.requests,
        max_prompt=min(args.prompt_len, args.max_len // 2),
        max_new_tokens=args.gen))
    t0 = time.time()
    try:
        rep = run_fleet_plan(router, plan)
    except (FleetAuditError, InvariantViolation, LifecycleError) as e:
        print(f"FLEET CHAOS FAIL: audit tripped: "
              f"{type(e).__name__}: {e}")
        return EXIT_CHAOS
    dt = time.time() - t0
    print(f"fleet chaos seed {args.chaos}: {rep.ticks} ticks in "
          f"{dt:.2f}s — states={rep.states} deaths={rep.deaths} "
          f"respawns={rep.respawns} migrated={rep.migrated} "
          f"drains={rep.drains} recovered={rep.recovered} "
          f"audits={rep.audits} backpressured={rep.backpressured}")
    if rep.ticks >= plan.cfg.max_ticks:
        print("FLEET CHAOS FAIL: fleet never drained (livelock)")
        return EXIT_CHAOS
    problems = _check_typed(rep.submitted)
    if problems:
        print("FLEET CHAOS FAIL: " + "; ".join(problems))
        return EXIT_CHAOS
    print("every request reached a terminal typed state; the fleet "
          "audit held every tick")
    if args.stats:
        _print_stats(router.stats())
    return 0


def _run_fleet(router, cfg, args) -> int:
    prompts = make_prompts(cfg.vocab, [args.prompt_len] * args.requests,
                           prefix_len=_prefix_len(args))
    reqs = [router.submit(p, max_new_tokens=args.gen, ttl=args.deadline)
            for p in prompts]
    t0 = time.time()
    cap = tick_cap(args)
    while not (router.drained() and all(r.terminal for r in reqs)) \
            and router.tick_no < cap:
        if args.kill_replica is not None and \
                router.tick_no + 1 == args.kill_replica:
            print(f"killing replica 0 at tick {args.kill_replica}")
            router.kill_replica(0, reason="--kill-replica")
        router.tick()
        router.audit()
    dt = time.time() - t0
    for r in reqs:
        print(f"req {r.rid}: {r.state.value:>9} on r{r.replica} "
              f"(migrations={r.migrations}) {r.tokens[:12]} ...")
    stats = router.stats()
    generated = sum(r.generated for r in reqs)
    recovered = sum(1 for r in reqs if r.migrations > 0
                    and r.state is RequestState.FINISHED)
    print(f"fleet: {stats['ticks']} ticks, {generated} tokens in "
          f"{dt:.2f}s ({generated / max(dt, 1e-9):.1f} tok/s on "
          f"{device_label()}); "
          f"deaths={stats['deaths']} respawns={stats['respawns']} "
          f"migrated={stats['migrated']} recovered={recovered} "
          f"drains={stats['drains']} rejoins={stats['rejoins']}")
    for idx, rs in stats["replicas"].items():
        print(f"  r{idx}: {rs['state']:>8} gen={rs['generation']} "
              f"load={rs['load']} hard_breaches={rs['hard_breaches']} "
              f"pages_in_use={rs['pages_in_use']}")
    if "prefix_hit_rate" in stats:
        print(f"fleet prefix cache: hit_rate={stats['prefix_hit_rate']:.2f} "
              f"({stats['prefix_hits']}/"
              f"{stats['prefix_hits'] + stats['prefix_misses']}), "
              f"{stats['prefix_tokens_reused']} tokens reused, "
              f"{stats['shared_pages']} shared pages fleet-wide")
    if args.stats:
        _print_stats(stats)
    problems = unfinished(reqs, args.gen)
    if router.tick_no >= cap and not router.drained():
        problems.insert(0, f"tick cap {cap} hit before the fleet drained")
    if problems:
        print("FLEET FAIL: " + "; ".join(problems))
        return EXIT_UNFINISHED
    return 0


def device_label() -> str:
    """``platform device_kind`` of the device the programs run on."""
    d = jax.devices()[0]
    return f"{d.platform} {d.device_kind}"


def make_prompts(vocab: int, lengths, *, prefix_len: int = 0,
                 seed: int = 42) -> list[list[int]]:
    """Seeded random prompts, one per entry of ``lengths``.  With
    ``prefix_len`` every prompt opens with the SAME system prefix of that
    many tokens (the total length is kept), so a prefix cache has
    something to share."""
    key = jax.random.key(seed)
    prefix = []
    if prefix_len:
        prefix = np.asarray(jax.random.randint(
            jax.random.fold_in(key, 999983), (prefix_len,), 0,
            vocab)).tolist()
    return [prefix + np.asarray(jax.random.randint(
                jax.random.fold_in(key, r), (max(n - len(prefix), 1),), 0,
                vocab)).tolist()
            for r, n in enumerate(lengths)]


def _prefix_len(args) -> int:
    # with --prefix-cache the workload models production traffic: every
    # prompt opens with the SAME system prefix (half the prompt length),
    # so the radix cache has something to share and the printed hit
    # rate / shared-page counts are meaningful
    return args.prompt_len // 2 if args.prefix_cache else 0


def tick_cap(args) -> int:
    """Livelock guard for clean serving: generous in the prefill chunks
    and decode steps every request needs, with room for failover."""
    chunks = -(-args.prompt_len // (args.page_size * args.chunk_pages))
    return 8 * (args.gen + args.requests + chunks)


def unfinished(reqs, gen: int) -> list[str]:
    """Clean-serving audit: every request must end FINISHED with exactly
    ``gen`` new tokens; anything else is a problem string."""
    problems = []
    for r in reqs:
        if r.state is not RequestState.FINISHED:
            problems.append(f"req {r.rid} ended {r.state.value}"
                            + (f" ({r.error})" if r.error else ""))
        elif r.generated != gen:
            problems.append(f"req {r.rid} generated {r.generated} of "
                            f"{gen} tokens")
    return problems


def serve_until_drained(server, max_ticks: int, on_tick=None
                        ) -> tuple[int, float, float]:
    """Tick ``server`` until its scheduler drains or ``max_ticks`` is
    reached.  ``on_tick(sched)`` runs after every tick.  Returns (ticks,
    seconds of the first tick, seconds of all ticks); each tick ends in
    a host read of its sampled tokens, so the walls cover device work."""
    sched = server.scheduler
    ticks, first, t0 = 0, 0.0, time.perf_counter()
    while not sched.drained() and ticks < max_ticks:
        server.tick()
        ticks += 1
        if ticks == 1:
            first = time.perf_counter() - t0
        if on_tick is not None:
            on_tick(sched)
    return ticks, first, time.perf_counter() - t0


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--kv-dtype", choices=("float32", "int8", "fp8"),
                    default="float32",
                    help="page-pool element type; int8/fp8 store "
                         "quantized pages with per-page scales, dequant "
                         "fused into the page-gather program (~4x cache "
                         "memory at bounded logit error)")
    ap.add_argument("--speculate", type=int, default=1, metavar="K",
                    help="speculative decode width: a draft model "
                         "proposes K-1 tokens and the target verifies "
                         "all K in ONE fused page-gather/verify launch "
                         "per step (requires greedy sampling)")
    ap.add_argument("--draft", default=None, metavar="ARCH",
                    help="draft model arch for --speculate (defaults to "
                         "--arch; must be attention-only)")
    ap.add_argument("--stats", action="store_true",
                    help="print per-request latency percentiles (TTFT / "
                         "inter-token p50/p99) and speculation "
                         "acceptance after the run")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy (default)")
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request TTL in seconds (TIMED_OUT beyond)")
    ap.add_argument("--queue-depth", type=int, default=None,
                    help="admission queue bound (backpressure beyond)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share prompt-prefix KV pages across requests "
                         "through the radix prefix cache (attention-only "
                         "stacks); the clean-serve workload gets a "
                         "shared system prefix so the hit rate is "
                         "observable")
    ap.add_argument("--chunk-pages", type=int, default=1,
                    help="prefill chunk budget per tick, in pages — "
                         "long prompts stream in between decode steps "
                         "instead of monopolizing admission")
    ap.add_argument("--check-invariants", action="store_true",
                    help="audit the page pool after every mutation")
    ap.add_argument("--guard-nan", action="store_true",
                    help="fail (only) slots producing non-finite logits")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="run a seeded fault plan instead of clean "
                         "serving (fleet faults when --replicas > 1); "
                         "exits nonzero on audit trip / non-typed end")
    ap.add_argument("--replicas", type=int, default=1,
                    help="in-process scheduler replicas behind the "
                         "fleet router (least-loaded admission, "
                         "health-checked failover)")
    ap.add_argument("--kill-replica", type=int, default=None,
                    metavar="TICK",
                    help="kill replica 0 at this fleet tick — its work "
                         "migrates and resumes elsewhere (needs "
                         "--replicas > 1)")
    args = ap.parse_args(argv)
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.kill_replica is not None and args.replicas < 2:
        ap.error("--kill-replica needs --replicas > 1 "
                 "(killing the only replica strands the work)")
    if args.speculate > 1 and args.temperature > 0.0:
        ap.error("--speculate requires greedy sampling "
                 "(drop --temperature)")
    return args


def load_model(args):
    """(cfg, params) for ``--arch``: the published geometry, or the
    reduced one with ``--smoke``; parameters from a fixed seed."""
    arch = get_arch(args.arch)
    cfg = arch.smoke if args.smoke else arch.model
    if cfg.encoder is not None:
        raise SystemExit("use whisper example for enc-dec serving")
    return cfg, init_params(cfg, jax.random.key(0))


def build_server(cfg, params, args):
    """A :class:`BatchedServer` with ``--requests`` slots, or a fleet
    router over ``--replicas`` of them."""
    kw = dict(temperature=args.temperature, top_k=args.top_k,
              queue_depth=args.queue_depth,
              guard_nan=args.guard_nan or args.chaos is not None,
              debug_invariants=args.check_invariants,
              prefix_cache=args.prefix_cache, chunk_pages=args.chunk_pages,
              kv_quant=None if args.kv_dtype == "float32" else args.kv_dtype)
    if args.speculate > 1:
        draft_arch = get_arch(args.draft or args.arch)
        draft_cfg = draft_arch.smoke if args.smoke else draft_arch.model
        kw.update(speculate=args.speculate, draft_cfg=draft_cfg,
                  draft_params=init_params(draft_cfg, jax.random.key(1)))
    if args.replicas > 1:
        from repro.serve.engine import make_fleet
        if args.chaos is not None:
            from repro.serve.chaos import StepClock
            # a quantized clock + a hard limit it dwarfs: determinism
            kw.update(clock=StepClock(), watchdog_hard_limit=30.0,
                      hard_breach_limit=1)
        return make_fleet(cfg, params, replicas=args.replicas,
                          slots=args.requests, max_len=args.max_len,
                          page_size=args.page_size, **kw)
    return BatchedServer(cfg, params, slots=args.requests,
                         max_len=args.max_len, page_size=args.page_size,
                         watchdog=StepWatchdog(), **kw)


def run(args) -> int:
    """Serve per ``args``; the process exit code.  Clean serving returns
    nonzero unless every request FINISHED with ``--gen`` tokens."""
    cfg, params = load_model(args)
    server = build_server(cfg, params, args)
    if args.replicas > 1:
        if args.chaos is not None:
            return _run_chaos_fleet(server, args)
        return _run_fleet(server, cfg, args)
    sched = server.scheduler
    if args.chaos is not None:
        return _run_chaos_single(sched, args)

    prompts = make_prompts(cfg.vocab, [args.prompt_len] * args.requests,
                           prefix_len=_prefix_len(args))
    reqs = [server.submit(p, max_new_tokens=args.gen, ttl=args.deadline)
            for p in prompts]
    cap = tick_cap(args)
    steps, _, dt = serve_until_drained(server, cap)
    generated = sum(r.generated for r in reqs)
    cache = sched.cache
    print(f"pages: {cache.pages_in_use()} in use of {cache.num_pages} "
          f"({cache.used_cache_bytes()} cache bytes backing live "
          f"requests)")
    for r in reqs:
        print(f"req {r.rid}: {r.state.value:>9} {r.tokens[:12]} ...")
    stats = sched.stats()
    print(f"{steps} ticks, {generated} tokens in {dt:.2f}s "
          f"({generated / max(dt, 1e-9):.1f} tok/s on {device_label()}); "
          f"preemptions={stats['preemptions']} "
          f"prefill_chunks={stats['prefill_chunks']} "
          f"decode_steps={stats['decode_steps']} "
          f"host_syncs={stats['host_syncs']} "
          f"watchdog_breaches={stats.get('watchdog_breaches', 0)}"
          + (" moe_routed={routed} moe_dropped={dropped} "
             "moe_per_expert={per_expert}".format(**stats["moe"])
             if "moe" in stats else ""))
    if "prefix" in stats:
        px = stats["prefix"]
        print(f"prefix cache: hit_rate={px['hit_rate']:.2f} "
              f"({px['hits']}/{px['hits'] + px['misses']}), "
              f"{px['tokens_reused']} tokens reused, "
              f"{stats['shared_pages']} shared pages, "
              f"{px['pages']} trie pages ({px['evicted']} evicted)")
    if args.stats:
        _print_stats(stats)
    problems = unfinished(reqs, args.gen)
    if not sched.drained():
        problems.insert(0, f"tick cap {cap} hit before the scheduler "
                           f"drained")
    if problems:
        print("SERVE FAIL: " + "; ".join(problems))
        return EXIT_UNFINISHED
    return 0


def main(argv=None) -> None:
    args = parse_args(argv)
    compile_cache.enable()
    raise SystemExit(run(args))


if __name__ == "__main__":
    main()
