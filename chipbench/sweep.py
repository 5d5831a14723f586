#!/usr/bin/env python3
"""Rate sweep: the load a workload's engine sustains, at rising rates.

    python3 chipbench/sweep.py --workload <name> --rates 0.2,0.3,0.4 --seconds 30 --seed <n>

One process sets the cell up once, then serves the cell's traffic mix at each
rate in turn (a lead-in, then ``--seconds`` measured), and prints one JSON
line per rate: arrivals and completions in the window, the backlog (requests
submitted and not finished) when the window opened and closed, output
tokens a second, and the TTFT and token-gap tails.  The mix's lead-in burst
is left out, so each rate starts from an idle engine.  The knee is the highest
rate whose backlog does not grow.  The sweep stops at the first rate whose
backlog grows by more than twice the lane count.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def backlog(run, at: float) -> int:
    return sum(1 for r in run.requests
               if r.submitted <= at and not (r.request.done and r.times and r.times[-1] <= at))


def main(argv=None) -> int:
    from chipbench import harness, reduce

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sess = harness.prepare(args.workload, args.seed, t_start=T_START)
    lanes = sess.cell.config["engine"]["max_batch"]
    print(json.dumps({"setup": sess.phases}), flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        tr = dict(sess.cell.traffic, arrival=dict(sess.cell.traffic["arrival"], rate_per_s=rate),
                  lead_in_burst=0)
        run = harness.measure(sess, args.seed + i, args.seconds, t_start=harness.clock(), traffic=tr)
        due = [r for r in run.requests if run.t_open <= r.due < run.t_close]
        done = [r for r in run.requests if r.request.done and r.times
                and run.t_open <= r.times[-1] < run.t_close]
        tt = reduce.ttfts(run)
        row = {
            "rate_per_s": rate, "arrived": len(due), "completed": len(done),
            "backlog_open": backlog(run, run.t_open), "backlog_close": backlog(run, run.t_close),
            "output_tok_per_s": reduce.tokens_in_window(run) / run.seconds,
            "ttft_p50_ms": 1e3 * reduce.percentile(tt, 50), "ttft_p90_ms": 1e3 * reduce.percentile(tt, 90),
            "itl_p95_ms": 1e3 * reduce.percentile(reduce.token_gaps(run), 95),
            "decode_step_ms": 1e3 * (reduce.median(reduce.decode_periods(run)) or float("nan")),
        }
        print(json.dumps(row), flush=True)
        if row["backlog_close"] - row["backlog_open"] > 2 * lanes:
            break
        sess.engine.drain(max_steps=sess.engine.steps + 3000)
    return 0


if __name__ == "__main__":
    sys.exit(main())
