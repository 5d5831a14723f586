#!/usr/bin/env python3
"""Bring-up check: serve phi4-mini-3.8b at full width on one TPU.

    python3 chip_smoke.py [--seed N]

Runs the serving main path once, in one process, through the entry points a
user calls: tune a deployment for the detected TPU (analytic table, seconds
on the host), open a ``KernelRuntime`` on it, build the paged
``ServingEngine`` with ``rt.serve(model, params)`` over full-width bf16
weights made from the seed, then ``submit`` four requests and ``drain()``.
Two requests share a 128-token prefix and the second arrives a step later,
so prefix aliasing and chunked prefill both run.

Each request's first-token logits are compared with the model's full
forward pass on a second runtime that runs the XLA reference path (Pallas
off) in float32 under ``jax.default_matmul_precision("highest")``.

The lines before the last are bring-up observations, not benchmark numbers.
The last line is ``{"ok": true, "device": {...}}``, printed only when every
check passed.  Otherwise the script exits non-zero with the reason on
stderr: no TPU backend, a device without a perf model, a dispatch incident
or quarantined config, an unfinished request, a serving program without a
Pallas kernel (``tpu_custom_call``), or logits outside the tolerance.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

try:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import registry
    from repro.core.families import family_names, get_family
    from repro.models import layers
    from repro.models.model import build_model
except ImportError as e:
    sys.exit(f"chip_smoke: FAIL: cannot import the program ({e}); "
             f"run this script from a checkout of the repository")

ARCH = "phi4-mini-3.8b"
PROMPT_LENS = (64, 200, 200, 512)
SHARED = (1, 2)  # these two prompts share their first SHARED_PREFIX tokens
SHARED_PREFIX = 128
LATE = 2  # submitted after the first step, so it can alias the shared blocks
MAX_NEW_TOKENS = 32
ENGINE = {"max_batch": 4, "cache_len": 1024, "block_size": 16}
# bf16 carries 8 significand bits (unit roundoff 2**-9 ~ 0.002).  Through 32
# residual layers with bf16 activations against a float32 forward on the same
# weights, the relative L2 error of the logits stays within a few percent;
# fp8-class rounding (2**-4) or a dropped term would land far above 0.05.
REL_L2_TOL = 0.05


def make_prompts(vocab: int, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, SHARED_PREFIX)
    prompts = []
    for i, n in enumerate(PROMPT_LENS):
        p = rng.integers(0, vocab, n)
        if i in SHARED:
            p[:SHARED_PREFIX] = prefix
        prompts.append(p.astype(np.int32))
    return prompts


def reference_first_logits(cfg, params, prompts, reference_runtime) -> np.ndarray:
    """Logits at each prompt's last token from one full forward pass in
    float32 (same bf16 weights) on the XLA reference path."""
    model = build_model(cfg, dtype=jnp.float32, param_dtype=jnp.bfloat16)
    width = max(len(p) for p in prompts)
    tokens = np.zeros((len(prompts), width), np.int32)  # right-padded: causal
    for i, p in enumerate(prompts):
        tokens[i, : len(p)] = p
    rows = np.array([len(p) - 1 for p in prompts], np.int32)

    def forward(params, tokens, rows):
        x, _ = model._forward(params, tokens)
        last = x[jnp.arange(x.shape[0]), rows][:, None]
        return layers.logits_from_hidden(params["emb"], last, cfg)[:, 0]

    with reference_runtime.activate(), jax.default_matmul_precision("highest"):
        out = jax.jit(forward)(params, tokens, rows)
    return np.asarray(out, np.float32)


def serve_and_compare(cfg, runtime, reference_runtime, *, seed: int = 0,
                      max_new_tokens: int = MAX_NEW_TOKENS) -> dict:
    """Serve the seeded requests on ``runtime`` and compare first-token
    logits with ``reference_runtime``'s full forward pass.  Returns a report;
    :func:`failures` reads it."""
    from jax import monitoring

    compile_s = [0.0]

    def on_duration(event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):  # trace, lower, compile
            compile_s[0] += secs

    monitoring.register_event_duration_secs_listener(on_duration)
    model = build_model(cfg)  # bf16 weights and activations
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.PRNGKey(seed)))
    init_s = time.perf_counter() - t0
    prompts = make_prompts(cfg.vocab, seed)
    first: dict[int, jax.Array] = {}
    decode_at: list[tuple[float, int]] = []  # (clock, width) per decode
    engine = runtime.serve(
        model, params, **ENGINE,
        on_first_token=lambda req, logits: first.__setitem__(req.uid, logits[0, -1]),
        on_decode=lambda width: decode_at.append((time.perf_counter(), width)),
    )
    compile_s[0] = 0.0
    t0 = time.perf_counter()
    tickets = {}
    for i, p in enumerate(prompts):
        if i != LATE:
            tickets[i] = engine.submit(p, max_new_tokens=max_new_tokens, uid=i)
    engine.step()
    tickets[LATE] = engine.submit(prompts[LATE], max_new_tokens=max_new_tokens, uid=LATE)
    status = engine.drain()
    serve_s = time.perf_counter() - t0
    serve_compile_s = compile_s[0]

    reqs = [tickets[i].request for i in range(len(prompts))]
    got = np.stack([np.asarray(first[i], np.float32) if i in first
                    else np.full(cfg.padded_vocab(), np.nan, np.float32)
                    for i in range(len(prompts))])
    ref = reference_first_logits(cfg, params, prompts, reference_runtime)
    rel_l2 = [float(np.linalg.norm(g - r) / np.linalg.norm(r)) for g, r in zip(got, ref)]
    tokens = sum(len(r.output) for r in reqs)
    # Decode period: time between consecutive full-width decodes (one whole
    # engine step each, host work and the previous step's token sync included).
    periods = [b[0] - a[0] for a, b in zip(decode_at, decode_at[1:])
               if a[1] == b[1] == ENGINE["max_batch"]]
    return {
        "init_s": init_s,
        "serve_s": serve_s,
        "serve_compile_s": serve_compile_s,
        "tokens": tokens,
        "decode_period_s": float(np.median(periods)) if periods else float("nan"),
        "status": status,
        "requests": reqs,
        "pool": engine.pool.stats(),
        "rel_l2": rel_l2,
        "argmax_agree": [int(g.argmax() == r.argmax()) for g, r in zip(got, ref)],
        "model": model,
        "params": params,
    }


def failures(report: dict, runtime) -> list[str]:
    """Every reason the served run is not right (empty when it is)."""
    out = []
    if runtime.incident_count() > 0:
        out.append(f"{runtime.incident_count()} dispatch incidents: "
                   f"{[i['error'] for i in runtime.incidents()][:3]}")
    if runtime.quarantined():
        out.append(f"quarantined configs: {[q['name'] for q in runtime.quarantined()]}")
    status = report["status"]
    undone = [r.uid for r in report["requests"] if not r.done]
    if undone or status.exhausted:
        out.append(f"requests not completed: {undone} (exhausted={status.exhausted})")
    bad = [i for i, e in enumerate(report["rel_l2"]) if not e <= REL_L2_TOL]
    if bad:
        out.append(f"first-token logits of requests {bad} disagree with the reference: "
                   f"relative L2 {[report['rel_l2'][i] for i in bad]} > {REL_L2_TOL}")
    return out


def lowered_programs(report: dict, runtime) -> dict[str, str]:
    """StableHLO of the engine's decode and prefill-chunk programs as traced
    under ``runtime`` (full batch width, one 512-token chunk)."""
    model, params = report["model"], report["params"]
    b, t = ENGINE["max_batch"], ENGINE["cache_len"]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    with runtime.activate():
        decode = jax.jit(model.decode_step).lower(
            params, jax.eval_shape(lambda: model.init_cache(b, t)), i32(b, 1), i32(b))
        chunk = jax.jit(model.prefill_chunk).lower(
            params, jax.eval_shape(lambda: model.init_cache(1, t)), i32(1, 512),
            i32(), i32())
    return {"decode": decode.as_text(), "prefill_chunk": chunk.as_text()}


def fail(reason: str) -> int:
    print(f"chip_smoke: FAIL: {reason}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="weights and prompts")
    args = ap.parse_args(argv)

    try:
        backend = jax.default_backend()
    except RuntimeError as e:
        return fail(f"no TPU: JAX backend failed to initialise ({e})")
    if backend != "tpu":
        return fail(f"no TPU: JAX backend is {backend!r}")

    import repro
    from repro.core.devices import detect_device
    from repro.core.perfmodel import DEVICES
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    cached = sum(1 for _ in Path(cache_dir).glob("*")) if Path(cache_dir).is_dir() else 0
    dev = jax.devices()[0]
    name = detect_device()
    if name not in DEVICES:
        return fail(f"device {dev.device_kind!r} canonicalises to {name!r}, "
                    f"which has no perf model (known: {sorted(DEVICES)})")
    print(f"jax {jax.__version__}; device {dev.device_kind!r} ({name}); "
          f"compile cache {cache_dir} ({cached} entries at start)")

    t0 = time.perf_counter()
    bundle = repro.tune([ARCH], devices=(name,), seed=args.seed)
    rt = bundle.runtime(device=name, strict=True)
    print(f"tuned {name} for {ARCH} in {time.perf_counter() - t0:.1f}s")
    for fam in family_names():
        path = rt.kernel_path(fam)
        why = get_family(fam).tpu_refusal if path == "xla" else None
        print(f"  kernel path {fam}: {path}"
              + (f" (TPU compiler refuses the kernel: {why})" if why else ""))
    ref_rt = repro.KernelRuntime(name="xla-reference")
    ref_rt.set_pallas_enabled(False)

    cfg = registry.get(ARCH)
    report = serve_and_compare(cfg, rt, ref_rt, seed=args.seed)
    problems = failures(report, rt)
    for prog, text in lowered_programs(report, rt).items():
        if "tpu_custom_call" not in text:
            problems.append(f"the {prog} program holds no Pallas kernel (tpu_custom_call)")

    s, pool = report["status"], report["pool"]
    steady_s = report["serve_s"] - report["serve_compile_s"]
    print("bring-up observations (not benchmark numbers):")
    print(f"  {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
          f"{cfg.n_params() / 1e9:.2f}B params bf16; init {report['init_s']:.1f}s")
    print(f"  served {s.completed} requests, {report['tokens']} tokens in "
          f"{report['serve_s']:.1f}s, of which {report['serve_compile_s']:.1f}s "
          f"tracing and compiling; {report['tokens'] / max(steady_s, 1e-9):.1f} tok/s "
          f"outside them; {s.steps} decode steps, median full-width step "
          f"{report['decode_period_s'] * 1e3:.1f} ms "
          f"({ENGINE['max_batch'] / report['decode_period_s']:.1f} tok/s)")
    print(f"  kv pool: {pool['n_blocks']} blocks x {pool['block_size']} tokens, "
          f"utilization {s.pool_utilization:.3f}, shared blocks {s.shared_blocks}, "
          f"prefix hit rate {s.prefix_hit_rate:.3f} "
          f"({s.prefix_hits}/{s.prefix_lookups})")
    print(f"  first-token logits vs reference: relative L2 "
          f"{[round(e, 5) for e in report['rel_l2']]} (tolerance {REL_L2_TOL}), "
          f"argmax agrees {report['argmax_agree']}")
    print(f"  incidents {rt.incident_count()}, quarantined {len(rt.quarantined())}, "
          f"health {s.health!r}")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"  peak HBM {stats['peak_bytes_in_use'] / 2**30:.2f} GiB of "
              f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    if problems:
        return fail("; ".join(problems))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
