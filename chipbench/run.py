#!/usr/bin/env python3
"""Chip benchmark: one run of one workload of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX sees a TPU.  Set-up
(tune, weights, engine, warm-up of the cell's shapes, lead-in traffic) is
followed by ``--seconds`` of open-loop traffic; ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a run
that also records a profiler trace of the window's last seconds.  Then a
sample of the served requests is checked against the configuration's plain
reference.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, ...,
``checks`` last); the checks, each number beside its limit, are also the last
lines of standard error.  Without a TPU, or with a device that the peaks table
lacks, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
OPS = {"max_logit_gap": "<=", "compared_tokens": ">=", "truncated_tokens": "<="}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="with --trace 1: also save the trace's event rows here (rows.json.gz)")
    args = ap.parse_args(argv)
    try:
        from chipbench import harness
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"chipbench: cannot import the benchmark or the program: {e}", file=sys.stderr)
        return 2
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                               t_start=T_START, keep_trace=args.keep_trace)
    except harness.HarnessError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(f"setup phases (s): {out['setup']}", file=sys.stderr)
    print(f"notes: {out['notes']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} {OPS.get(name, '<=')} {c['limit']}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
