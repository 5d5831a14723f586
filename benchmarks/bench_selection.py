"""Selection fast-path microbenchmark: CART fit time + dispatch throughput.

Tracks the two costs the paper says must be negligible (§5.1, and the
companion case study's retuning economics):

  * **fit** — CART training on the synthetic tuning dataset, new vectorized
    Gini sweep vs the seed per-threshold Python loop (vendored below as the
    baseline so the speedup stays measurable forever);
  * **predict** — batch classification of 10k feature rows, flat-array
    frontier descent vs the seed per-row nested walk;
  * **dispatch** — policy selections/sec through a ``KernelRuntime`` handle,
    cold (featurize+predict every call) vs shape-cache-hit;
  * **handle vs legacy** — the same warm dispatch through an explicit
    ``KernelRuntime`` handle vs the deprecated module-level
    ``repro.kernels.ops`` shim path (which resolves the current runtime per
    call).  Gated in ``perf_gate.py`` so the api_redesign's indirection can
    never quietly eat the PR-1 compiled fast path.
  * **guarded dispatch overhead** — a full ``ops.matmul`` call (select +
    kernel under the DESIGN.md §11 fault guard, everything disarmed) vs the
    identical dispatch body with the guard frame deleted.  Gated at 5% in
    ``perf_gate.py``: robustness must stay ~free on the happy path.

Run:  PYTHONPATH=src python benchmarks/bench_selection.py [--smoke] [--json out]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.classify import DecisionTreeClassifier
from repro.core.dataset import build_model_dataset, problem_features, synthetic_problems
from repro.core.dispatch import build_labels, train_deployment
from repro.core.runtime import KernelRuntime, current_runtime, default_runtime
from repro.core.selection import select_from_dataset
from repro.kernels import ops


# ---------------------------------------------------------------------------
# Vendored seed implementation (pre-fast-path), kept verbatim as the baseline.
# ---------------------------------------------------------------------------
class SeedDecisionTree(DecisionTreeClassifier):
    """The seed CART: per-threshold Python inner loop + per-row nested walk."""

    def fit(self, x, y, sample_weight=None):
        x = np.asarray(x, dtype=np.float64)
        y = np.asarray(y, dtype=int)
        self.n_classes_ = int(y.max()) + 1 if y.size else 1
        rng = np.random.default_rng(self.seed)
        w = np.ones(len(y)) if sample_weight is None else np.asarray(sample_weight, float)
        self.root_ = self._grow(x, y, w, depth=0, rng=rng)
        return self

    def _grow(self, x, y, w, depth, rng):
        from repro.core.classify import _Node

        node = _Node()
        counts = np.bincount(y, weights=w, minlength=self.n_classes_)
        node.counts = counts
        node.label = int(counts.argmax())
        if (
            (self.max_depth is not None and depth >= self.max_depth)
            or len(y) < 2 * self.min_samples_leaf
            or counts.max() == counts.sum()
        ):
            return node
        nf = x.shape[1]
        feats = np.arange(nf)
        if self.max_features is not None and self.max_features < nf:
            feats = rng.choice(nf, size=self.max_features, replace=False)
        best = None
        parent_gini = self._gini(counts)
        for f in feats:
            order = np.argsort(x[:, f], kind="stable")
            xs, ys, ws = x[order, f], y[order], w[order]
            onehot = np.zeros((len(ys), self.n_classes_))
            onehot[np.arange(len(ys)), ys] = ws
            left_csum = np.cumsum(onehot, axis=0)
            total = left_csum[-1]
            for i in range(self.min_samples_leaf, len(ys) - self.min_samples_leaf + 1):
                if i < len(ys) and xs[i - 1] == xs[min(i, len(ys) - 1)]:
                    continue
                lc = left_csum[i - 1]
                rc = total - lc
                nl, nr = lc.sum(), rc.sum()
                if nl <= 0 or nr <= 0:
                    continue
                g = (nl * self._gini(lc) + nr * self._gini(rc)) / (nl + nr)
                if best is None or g < best[0]:
                    thr = 0.5 * (xs[i - 1] + xs[min(i, len(ys) - 1)])
                    best = (g, int(f), float(thr))
        if best is None or best[0] >= parent_gini - 1e-12:
            return node
        _, f, thr = best
        mask = x[:, f] <= thr
        if mask.sum() < self.min_samples_leaf or (~mask).sum() < self.min_samples_leaf:
            return node
        node.feature, node.threshold = f, thr
        node.left = self._grow(x[mask], y[mask], w[mask], depth + 1, rng)
        node.right = self._grow(x[~mask], y[~mask], w[~mask], depth + 1, rng)
        return node


def _best_of(fn, reps: int) -> float:
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def _best_of_pair(fn_a, fn_b, reps: int) -> tuple[float, float]:
    """Interleaved best-of timing: A/B alternate so background load skews
    both sides equally, and the pair order flips each rep so neither side
    always pays the first-in-pair cache/branch-warmup cost (measured at a
    systematic ~4-6us on eager JAX dispatch — enough to fake a 5% "overhead"
    between byte-identical code paths)."""
    ta, tb = [], []
    for i in range(reps):
        pair = (fn_a, ta), (fn_b, tb)
        for fn, acc in pair if i % 2 == 0 else reversed(pair):
            t0 = time.perf_counter()
            fn()
            acc.append(time.perf_counter() - t0)
    return min(ta), min(tb)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny sizes for CI")
    ap.add_argument("--json", default=None, help="write results to this path")
    ap.add_argument("--reps", type=int, default=None,
                    help="override timing repetitions (CI perf-gate uses a few "
                         "reps even at --smoke sizes to damp scheduler noise)")
    args = ap.parse_args(argv)

    n_problems = 80 if args.smoke else 300
    n_predict = 2_000 if args.smoke else 10_000
    n_dispatch = 500 if args.smoke else 5_000
    reps = args.reps if args.reps else (1 if args.smoke else 3)

    ds = build_model_dataset(synthetic_problems(n_problems))
    chosen = select_from_dataset(ds, 8, "topn", "standard")
    feats = ds.features
    labels = build_labels(ds.perf, chosen)
    print(f"tuning dataset: {feats.shape[0]} problems x {len(ds.configs)} configs, "
          f"{len(chosen)} deployed")

    # -- fit: DecisionTreeA (unlimited depth) on the tuning dataset ----------
    t_seed, t_fast = _best_of_pair(
        lambda: SeedDecisionTree().fit(feats, labels),
        lambda: DecisionTreeClassifier().fit(feats, labels),
        reps if (args.smoke or args.reps) else 7,
    )
    fit_speedup = t_seed / t_fast
    print(f"fit   seed {t_seed * 1e3:8.1f} ms   vectorized {t_fast * 1e3:8.1f} ms   "
          f"speedup {fit_speedup:6.1f}x")

    # -- predict: 10k rows, per-row nested walk vs flat frontier descent ----
    clf = DecisionTreeClassifier().fit(feats, labels)
    rng = np.random.default_rng(0)
    big = feats[rng.integers(0, len(feats), size=n_predict)]
    t_walk, t_flat = _best_of_pair(
        lambda: clf.predict_nested(big), lambda: clf.predict(big), reps
    )
    np.testing.assert_array_equal(clf.predict(big), clf.predict_nested(big))
    pred_speedup = t_walk / t_flat
    print(f"pred  nested {t_walk * 1e3:6.1f} ms   flat {t_flat * 1e3:12.1f} ms   "
          f"speedup {pred_speedup:6.1f}x   ({n_predict} rows)")

    # -- dispatch: selections/sec, cold vs shape-cache-hit -------------------
    dep = train_deployment(ds, chosen, "DecisionTreeA")
    rt = KernelRuntime(name="bench-selection")
    rt.install(dep)
    shapes = [tuple(int(v) for v in p) for p in ds.problems]

    def cold():
        rt.clear_shape_cache()
        for i in range(n_dispatch):
            m, k, n, b = shapes[i % len(shapes)]
            # bypass the cache: a fresh shape key every call
            dep.select_matmul(m, k, n, b)

    def warm():
        rt.clear_shape_cache()
        for i in range(n_dispatch):
            m, k, n, b = shapes[i % len(shapes)]
            rt.select_matmul_config(m, k, n, b)

    t_cold = _best_of(cold, reps)
    t_warm = _best_of(warm, reps)
    stats = rt.shape_cache_stats()
    assert stats["hits"] >= n_dispatch - len(shapes), stats
    cold_rate = n_dispatch / t_cold
    warm_rate = n_dispatch / t_warm
    print(f"disp  cold {cold_rate:10.0f} sel/s   cached {warm_rate:10.0f} sel/s   "
          f"speedup {warm_rate / cold_rate:6.1f}x   "
          f"(cache: {stats['hits']} hits / {stats['misses']} misses)")

    # -- handle vs legacy-global: the api_redesign dispatch microbench -------
    # Same deployment, same warm shapes: explicit KernelRuntime methods vs
    # the deprecated ops.* shim (one extra current_runtime() resolution per
    # call).  The ratio should sit near 1.0; a fall-off means the redesign's
    # indirection started taxing the serving fast path.
    default_runtime().install(dep)  # the shims' target (no deprecated call)
    # All-cache-hit loops are so fast that n_dispatch iterations time ~1 ms;
    # stretch the timed region and interleave more reps so one scheduler
    # preemption cannot flip the gated ratio.
    n_ab = n_dispatch * 4
    try:
        def handle():
            for i in range(n_ab):
                m, k, n, b = shapes[i % len(shapes)]
                rt.select_matmul_config(m, k, n, b)

        def legacy():
            for i in range(n_ab):
                m, k, n, b = shapes[i % len(shapes)]
                ops.select_matmul_config(m, k, n, b)

        handle()  # prime both caches outside the timed region
        legacy()
        t_handle, t_legacy = _best_of_pair(handle, legacy, max(reps, 5))
    finally:
        default_runtime().install(None)
    handle_rate = n_ab / t_handle
    legacy_rate = n_ab / t_legacy
    runtime_ratio = handle_rate / legacy_rate
    print(f"disp  handle {handle_rate:8.0f} sel/s   legacy shim {legacy_rate:8.0f} sel/s   "
          f"handle/legacy {runtime_ratio:5.2f}x")

    # -- guarded dispatch overhead: the fault guard's happy-path tax ---------
    # ops.matmul runs select + jnp.dot inside _guarded_call (injection sites,
    # non-finite validation, and the circuit breaker all disarmed: no fault
    # plan, no quarantine entries); the plain loop replicates the op's full
    # dispatch body — shape featurization, selection, the same jnp.dot — with
    # the guard frame deleted, so the ratio isolates exactly what the fault
    # guard adds and nothing the op wrapper always cost.  Each pair runs
    # back-to-back in the same scheduler window and the median of per-pair
    # ratios is taken: a min over all pairs would let the two sides pick
    # their minima from *different* windows, which on a loaded box fakes a
    # 10%+ "overhead" between code paths that differ by nothing.
    import jax.numpy as jnp

    xg = jnp.ones((64, 128), jnp.float32)
    wg = jnp.ones((128, 64), jnp.float32)
    n_guard = max(n_dispatch // 2, 200)

    def _matmul_unguarded(lhs, rhs):
        # ops.matmul's dispatch body with _guarded_call stripped — keep in
        # sync with repro.kernels.ops.matmul so the comparison stays honest.
        r = current_runtime()
        *lead, k = lhs.shape
        n = rhs.shape[1]
        m = lead[-1] if lead else 1
        batch = 1
        for d in lead[:-1]:
            batch *= d
        r.select_matmul_config(m, k, n, batch)
        r.kernel_path("matmul")
        return jnp.dot(lhs, rhs, preferred_element_type=jnp.float32).astype(lhs.dtype)

    with rt.activate():
        def guarded():
            for _ in range(n_guard):
                ops.matmul(xg, wg)

        def plain():
            for _ in range(n_guard):
                _matmul_unguarded(xg, wg)

        guarded()  # prime compile/dispatch + shape caches outside the timing
        plain()
        pairs = []
        for i in range(max(reps * 3, 9)):
            order = (guarded, plain) if i % 2 == 0 else (plain, guarded)
            t = {}
            for fn in order:
                t0 = time.perf_counter()
                fn()
                t[fn] = time.perf_counter() - t0
            pairs.append((t[guarded], t[plain]))
    ratios = sorted(tg / tp for tg, tp in pairs)
    guard_overhead = ratios[len(ratios) // 2]
    t_guard = min(tg for tg, _ in pairs)
    t_plain = min(tp for _, tp in pairs)
    print(f"disp  guarded {t_guard / n_guard * 1e6:7.1f} us/call   "
          f"plain {t_plain / n_guard * 1e6:7.1f} us/call   "
          f"overhead {guard_overhead:5.3f}x   (budget 1.05x)")

    results = {
        "n_problems": n_problems,
        "fit_seed_s": t_seed,
        "fit_fast_s": t_fast,
        "fit_speedup": fit_speedup,
        "predict_rows": n_predict,
        "predict_nested_s": t_walk,
        "predict_flat_s": t_flat,
        "predict_speedup": pred_speedup,
        "dispatch_cold_per_s": cold_rate,
        "dispatch_cached_per_s": warm_rate,
        "dispatch_speedup": warm_rate / cold_rate,
        "dispatch_handle_per_s": handle_rate,
        "dispatch_legacy_per_s": legacy_rate,
        "runtime_dispatch_ratio": runtime_ratio,
        "guarded_call_us": t_guard / n_guard * 1e6,
        "plain_call_us": t_plain / n_guard * 1e6,
        "guarded_dispatch_overhead": guard_overhead,
    }
    if args.json:
        from pathlib import Path

        Path(args.json).parent.mkdir(parents=True, exist_ok=True)
        Path(args.json).write_text(json.dumps(results, indent=1))
        print(f"wrote {args.json}")
    # Regression tripwire: quiet machines measure 10-12x; a genuine fall
    # back to the per-threshold-loop implementation would read ~1x.  The
    # guard sits below the noise floor so scheduler jitter can't trip it.
    if not args.smoke and fit_speedup < 8:
        raise SystemExit(f"fit speedup regressed: {fit_speedup:.1f}x (expect ~10-12x)")
    return results


if __name__ == "__main__":
    main()
