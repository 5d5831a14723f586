"""Tuning CLI: produce a shippable kernel deployment (or multi-device bundle).

The operator tool for new-hardware bring-up (the paper's zero-developer-
effort pitch):

  python -m repro.launch.tune --device tpu_v5e --out deploy.json
  python -m repro.launch.tune --device host_cpu --out deploy.json   # measured
  python -m repro.launch.tune --device tpu_v5e --archs granite-8b,glm4-9b

Every registered kernel family (matmul, attention, wkv, ssm_scan, ...) is
tuned into the artifact; ``--families`` restricts the set.  Fleet mode packs
one Deployment per device into a single v5 bundle any host auto-installs for
its detected hardware:

  python -m repro.launch.tune --devices tpu_v5e,tpu_v4 --bundle bundle.json

New hardware can be brought up cheaply through the staged pipeline
(DESIGN.md §12): ``--transfer-from deploy_v5e.json`` warm-starts from a tuned
sibling's artifact and measures only where model and sibling disagree,
``--prune-ratio 0.5`` drops the half of the config space the perf model rules
out before any measurement, and ``--measure-budget 0.3`` hard-caps measured
cells at 30% of a full harvest (``--measure-budget auto`` sizes the cap per
device from the donor's recorded lineage ``model_error``).  Fleet mode chains
transfers automatically with ``--transfer`` (donors tune first, siblings
warm-start off them).

Artifacts are consumed by trainers/servers via ``--deployment`` / ``--bundle``
launcher flags or ``repro.core.bundle.install_bundle(path)``.
"""
from __future__ import annotations

import argparse

from repro.configs import registry
from repro.core.cluster import CLUSTER_METHODS
from repro.core.normalize import NORMALIZATIONS
from repro.core.tuner import save_fleet, save_result, tune, tune_fleet, tune_for_archs
from repro.launch.compile_cache import use_compile_cache


def _measure_budget(text: str):
    """argparse type for --measure-budget: a fraction in (0, 1) or 'auto'."""
    if text.strip().lower() == "auto":
        return "auto"
    try:
        val = float(text)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected a fraction in (0, 1) or 'auto', got {text!r}"
        ) from None
    if not 0.0 < val < 1.0:
        raise argparse.ArgumentTypeError(
            f"must be a fraction in (0, 1) or 'auto', got {val}"
        )
    return val


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--device", default="tpu_v5e", choices=["tpu_v5e", "tpu_v4", "host_cpu"])
    ap.add_argument("--devices", default=None,
                    help="comma-separated device names to tune into one bundle (fleet mode)")
    ap.add_argument("--archs", default=None, help="comma-separated arch ids (default: all)")
    ap.add_argument("--families", default=None,
                    help="comma-separated kernel families to tune beyond matmul "
                         "(default: every registered family; see repro.core.families)")
    ap.add_argument("--n-kernels", type=int, default=8)
    ap.add_argument("--method", default="pca_kmeans", choices=CLUSTER_METHODS)
    ap.add_argument("--normalization", default="standard", choices=NORMALIZATIONS)
    ap.add_argument("--classifier", default="DecisionTreeA")
    ap.add_argument("--max-problems", type=int, default=300)
    ap.add_argument("--cpu-problems", type=int, default=24)
    ap.add_argument("--out", default=None, help="single-device deployment output path")
    ap.add_argument("--bundle", default=None, help="multi-device bundle output path")
    ap.add_argument("--transfer-from", default=None, metavar="DEPLOY_JSON",
                    help="warm-start from a tuned sibling's deployment artifact: reuse "
                         "its kernel subset as clustering seeds and measure only where "
                         "the perf model and the sibling disagree (single-device mode)")
    ap.add_argument("--transfer", action="store_true",
                    help="fleet mode: tune donors first and warm-start each remaining "
                         "device from its nearest tuned sibling (devices.FALLBACKS)")
    ap.add_argument("--prune-ratio", type=float, default=None, metavar="R",
                    help="keep only the top R (0<R<1) of the config space by predicted "
                         "perf before measuring anything")
    ap.add_argument("--measure-budget", type=_measure_budget, default=None, metavar="B",
                    help="measure at most B (0<B<1) of the full harvest's cells; the "
                         "rest is filled from the perf model.  'auto' sizes B per "
                         "device from its donor's recorded lineage model_error "
                         "(donor-less tunes measure in full)")
    args = ap.parse_args(argv)
    use_compile_cache()

    if not args.out and not args.bundle:
        ap.error("one of --out / --bundle is required")
    if args.devices and not args.bundle:
        ap.error("--devices selects fleet mode and requires --bundle <path>")
    if args.prune_ratio is not None and not 0.0 < args.prune_ratio < 1.0:
        ap.error(f"--prune-ratio must be a fraction in (0, 1), got {args.prune_ratio}")
    if args.transfer_from and args.device == "host_cpu":
        ap.error("--transfer-from does not apply to host_cpu (it always measures)")
    transfer_prior = None
    if args.transfer_from:
        from repro.core.dispatch import Deployment

        transfer_prior = Deployment.load(args.transfer_from)

    archs = args.archs.split(",") if args.archs else None
    if archs:
        for a in archs:
            registry.get(a)  # validate early
    families = None
    if args.families is not None:
        from repro.core.families import get_family

        families = [f for f in args.families.replace(" ", "").split(",") if f]
        for f in families:
            get_family(f)  # validate early

    if args.bundle:
        device_names = tuple(
            (args.devices or "tpu_v5e,tpu_v4").replace(" ", "").split(",")
        )
        fleet = tune_fleet(
            archs, device_names=device_names, n_kernels=args.n_kernels,
            method=args.method, normalization=args.normalization,
            classifier=args.classifier, max_problems=args.max_problems,
            cpu_problems=args.cpu_problems, families=families,
            transfer=args.transfer, prune_ratio=args.prune_ratio,
            measure_budget=args.measure_budget,
        )
        save_fleet(fleet, args.bundle)
        print(f"bundle ({len(fleet.results)} devices) -> {args.bundle}")
        for name, res in sorted(fleet.results.items()):
            print(f"  {name}: oracle {res.oracle_fraction:.1%} / "
                  f"classifier {res.classifier_fraction:.1%} "
                  f"(families: {', '.join(res.deployment.family_names())})")
        # Prove the saved artifact serves: load it back into a fresh, isolated
        # KernelRuntime (nothing process-global is touched) and dispatch one
        # probe selection against the first tuned device.
        import repro

        rt = repro.load_bundle(args.bundle).runtime(device=device_names[0])
        probe = rt.select_matmul_config(512, 784, 512, 16)
        if probe is None:
            raise SystemExit(
                f"bundle verification failed: {args.bundle} loaded into {rt!r} "
                f"but served no probe selection"
            )
        print(f"verified: {rt!r} serves (probe matmul -> {probe.name()})")
        if not args.out:
            return
    if args.device == "host_cpu":
        from repro.core.cpubench import build_cpu_dataset, cpu_problems

        print(f"measuring {args.cpu_problems} problems x 210 configs on this host...")
        ds = build_cpu_dataset(cpu_problems(args.cpu_problems), verbose=True)
        result = tune(
            ds, n_kernels=args.n_kernels, method=args.method,
            normalization=args.normalization, classifier=args.classifier,
            arch_ids=archs, families=families,
        )
    else:
        result = tune_for_archs(
            archs, device_name=args.device, n_kernels=args.n_kernels,
            method=args.method, normalization=args.normalization,
            classifier=args.classifier, max_problems=args.max_problems,
            families=families, transfer_from=transfer_prior,
            prune_ratio=args.prune_ratio, measure_budget=args.measure_budget,
        )
    save_result(result, args.out)
    dep = result.deployment
    print(f"deployment -> {args.out}")
    for fname in dep.family_names():
        configs, _tree = dep.family_tuning(fname)
        print(f"  {fname:9s} kernels: {[c.name() for c in configs]}")
    print(f"  oracle {result.oracle_fraction:.1%} / classifier {result.classifier_fraction:.1%}")
    lineage = dep.meta.get("tuning_lineage") or {}
    rec = lineage.get("matmul")
    if rec and rec.get("measured_fraction", 1.0) < 1.0:
        src = rec.get("source_device") or "model only"
        print(f"  staged: measured {rec['measured_fraction']:.1%} of a full harvest "
              f"(donor: {src}, kept {rec['prune_ratio']:.0%} of config space)")


if __name__ == "__main__":
    main()
