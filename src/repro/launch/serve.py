"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Brings up the paged serving engine with the tuned kernel deployment and
drives a batch of synthetic requests through the submit/stream API
(prefill + continuous decode, optional latency targets).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core.retune import DEFAULT_DRIFT_THRESHOLD, DEFAULT_MIN_EVENTS
from repro.core.runtime import KernelRuntime
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build_model
from repro.serve.engine import ServingEngine


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=sorted(registry.ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU); default: the published widths")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=None, metavar="TOKENS",
                    help="paged KV cache block size (divides --cache-len; "
                         "default: one dense block per lane)")
    ap.add_argument("--n-blocks", type=int, default=None,
                    help="total KV pool blocks (default: lanes * blocks/lane)")
    ap.add_argument("--latency-target-ms", type=float, default=None,
                    help="per-token latency SLO attached to every other "
                         "request (exercises objective-aware selection)")
    ap.add_argument("--deployment", default=None, help="single-device Deployment json")
    ap.add_argument("--bundle", default=None,
                    help="multi-device DeploymentBundle json (auto-installs for this host)")
    ap.add_argument("--serve-device", default=None,
                    help="override device name for --bundle resolution (default: detect)")
    ap.add_argument("--retune-interval", type=int, default=None, metavar="STEPS",
                    help="check telemetry drift every N decode steps and "
                         "incrementally retune + hot-swap the policy when it fires")
    ap.add_argument("--drift-threshold", type=float, default=DEFAULT_DRIFT_THRESHOLD,
                    help="Jensen-Shannon divergence (0-1) that triggers a retune")
    ap.add_argument("--retune-min-events", type=int, default=DEFAULT_MIN_EVENTS,
                    help="telemetry floor before a drift check may trigger")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="fault-injection plan 'site:kind[:times[:after]],...' — e.g. "
                         "'dispatch.matmul:compile_error,engine.prefill:compile_error'; "
                         "injected faults are contained by the dispatch guard "
                         "(DESIGN.md §11) and reported after the run (nan/inf "
                         "kinds poison concrete values only, so they are no-ops "
                         "inside jit-traced serving programs)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the fault plan's probabilistic specs")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = registry.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    # The launcher owns an explicit runtime handle: every policy, cache, and
    # telemetry mutation below is scoped to it (nothing process-global).
    rt = KernelRuntime(name=f"serve[{args.arch}]")
    if args.chaos:
        from repro.core.faults import FaultPlan

        rt.set_fault_plan(FaultPlan.parse(args.chaos, seed=args.chaos_seed))
    bundle = None
    if args.bundle:
        from repro.core.bundle import DeploymentBundle

        bundle = DeploymentBundle.load(args.bundle)
    elif args.deployment:
        from repro.core.dispatch import Deployment

        rt.install(Deployment.load(args.deployment))

    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))

    extra = {}
    if cfg.family == "vlm":
        extra["image_embs"] = jnp.zeros((1, cfg.n_image_tokens, cfg.d_model), model.dtype)
    if cfg.family == "audio":
        extra["frames"] = jnp.zeros((1, 32, cfg.d_model), model.dtype)

    engine = ServingEngine(
        model, params, max_batch=args.max_batch, cache_len=args.cache_len,
        block_size=args.block_size, n_blocks=args.n_blocks,
        extra_inputs=extra, bundle=bundle, device=args.serve_device, runtime=rt,
        retune_interval=args.retune_interval, drift_threshold=args.drift_threshold,
        retune_min_events=args.retune_min_events,
    )
    if bundle is not None:
        print(f"bundle installed: serving with the {engine.device!r} deployment")
    rng = np.random.default_rng(0)
    t0 = time.time()
    tickets = [
        engine.submit(
            rng.integers(0, cfg.vocab, size=8).astype(np.int32),
            max_new_tokens=args.max_new_tokens,
            latency_target_ms=args.latency_target_ms if i % 2 else None,
        )
        for i in range(args.requests)
    ]
    status = engine.drain()
    dt = time.time() - t0
    reqs = [t.request for t in tickets]
    toks = sum(len(r.output) for r in reqs)
    print(f"served {len(reqs)} requests, {toks} tokens, {dt:.2f}s "
          f"({toks / max(dt, 1e-9):.1f} tok/s), {engine.steps} decode steps")
    pool = engine.pool.stats()
    print(f"kv pool: {pool['n_blocks']} blocks x {pool['block_size']} tokens, "
          f"{pool['used_blocks']} in use at drain ({pool['utilization']:.0%}), "
          f"{status.preempted} requests preempted")
    if engine.slo_events:
        print(f"slo: {len(engine.slo_events)} mode transitions under "
              f"latency target {args.latency_target_ms} ms")
    # Dispatch evidence: nonzero counters prove the traces consulted the
    # installed policy (the counters only move when a policy is live).
    stats = rt.shape_cache_stats()
    print(f"policy selections at trace time: {stats['hits'] + stats['misses']} "
          f"({stats['hits']} shape-cache hits) on runtime {rt.name!r}")
    if status.exhausted:
        print(f"WARNING: step budget exhausted with {status.in_flight} in-flight / "
              f"{status.queued} queued requests unfinished")
    for ev in engine.retune_events:
        if ev.swapped:
            verdict = f"retuned {'+'.join(ev.families) or 'matmul'} + hot-swapped"
        elif ev.drift_score >= args.drift_threshold:
            verdict = f"below event floor ({ev.n_events}/{args.retune_min_events})"
        else:
            verdict = "no drift"
        print(f"  retune check @ step {ev.step}: drift {ev.drift_score:.3f} "
              f"(unseen {ev.unseen_fraction:.1%}) -> {verdict} "
              f"[{ev.n_configs} kernels, policy epoch {ev.epoch}]")
    if args.chaos:
        plan = rt.fault_plan
        print(f"chaos: {len(plan.events)} faults fired, {rt.incident_count()} "
              f"incidents contained, {len(rt.quarantined())} configs in "
              f"quarantine, engine health {status.health!r}")
        for inc in rt.incidents()[-5:]:
            print(f"  incident #{inc['seq']} {inc['site']} [{inc['config']}] "
                  f"-> {inc['action']}: {inc['error']}")
    for r in reqs[:3]:
        print(f"  req {r.uid}: {r.output[:10]}...")


if __name__ == "__main__":
    main()
