#!/usr/bin/env python3
"""The control of the correctness check: fp8 in the program's place.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 --seconds 40

For each seed, one whole run of the cell (set-up, lead-in, window at the
cell's own load) whose check puts the token that the fp8 reference ranks
first at each served position in the served token's place: ``correct`` has
to come out false, the control's widest gap above the limit in
``limits/<workload>.json``.  The program's own widest gap on the same sampled
requests, what a benchmark run compares, is read beside it.  Prints one JSON
line per seed.  The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    from chipbench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_start=harness.clock(), control=True)
        print(json.dumps({"seed": seed, "program": out["control"]["program_max_logit_gap"],
                          "control": out["checks"]["max_logit_gap"]["value"],
                          "compared_tokens": out["checks"]["compared_tokens"]["value"],
                          "correct": out["correct"], "metrics": out["metrics"],
                          "memory_peak_bytes": out["device"]["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
