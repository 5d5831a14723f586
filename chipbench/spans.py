#!/usr/bin/env python3
"""One traced window of a cell with the program's own spans on.

    python3 chipbench/spans.py --workload <name> --seed <n> --seconds <s> [--keep-trace DIR]

Set-up and window as a ``run.py --trace 1`` run, with ``repro.trace.record()``
open around the lead-in and the window, so the program's ``serve.*`` and
``kvpool.*`` spans land on the host plane of the profiler trace of the
window's last seconds.  Prints one JSON line: the cell's per-layer metrics as
the benchmark's readers read them (with the spans' cost in them), and from
``program_trace``: the device ms a full-width decode step spends in the KV
pool's eager ops, the device ms of one decode program, the share of the step
time the device idles while the host dispatches, the idle ms per step by
innermost host span, the longest idle gaps so named, device ms per step by
program, and the host's own ms per step in each span (``self_ms``).  No
correctness check runs.  With ``--keep-trace``, the rows of the last 12
traced steps go to ``DIR/rows.json.gz`` and a list of the trace's planes and
lines, with a few events of each, to ``DIR/lines.json``.  The benchmark's
own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def self_ms(rec, run, width: int) -> dict[str, float]:
    """Mean host ms per window step that decoded at ``width``, by span name,
    each span less the spans inside it."""
    spans = rec.spans
    kids: dict[int, float] = {}
    for s in spans:
        if s.parent is not None:
            kids[s.parent] = kids.get(s.parent, 0.0) + s.end - s.start
    root_of = {}
    for i, s in enumerate(spans):
        root_of[i] = i if s.parent is None else root_of[s.parent]
    roots = {i for i, s in enumerate(spans) if s.parent is None
             and run.t_open <= s.start < run.t_close}
    full = {root_of[i] for i, s in enumerate(spans)
            if s.name == "serve.decode" and s.attrs["width"] == width and root_of[i] in roots}
    out: dict[str, float] = {}
    for i, s in enumerate(spans):
        if root_of[i] in full:
            out[s.name] = out.get(s.name, 0.0) + 1e3 * (s.end - s.start - kids.get(i, 0.0))
    return {k: v / len(full) for k, v in sorted(out.items(), key=lambda kv: -kv[1])} if full else {}


def catalog(path: Path) -> list[dict]:
    """Planes and lines of a trace: event counts, the commonest names, and
    the first three events of each line."""
    from collections import Counter

    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs = list(line.events)
            out.append({"plane": plane.name, "line": line.name, "events": len(evs),
                        "names": Counter(e.name[:80] for e in evs).most_common(12),
                        "first": [{"name": e.name[:300], "start_ns": e.start_ns,
                                   "duration_ns": e.duration_ns,
                                   "stats": {k: str(v)[:120] for k, v in e.stats}}
                                  for e in evs[:3]]})
    return out


def last_steps(rows: list[list], n: int) -> list[list]:
    from chipbench import trace_reduce as tr

    steps = sorted(r[3] for r in rows if r[2] == tr.STEP_SPAN)[-n:]
    return [r for r in rows if steps and r[3] >= steps[0]]


def measure(sess, seed: int, seconds: float, *, t_start: float,
            keep_trace: Path | None = None) -> dict:
    """One window of ``sess``'s cell with the program's spans recorded and
    the last seconds traced; returns what ``main`` prints."""
    from chipbench import harness, program_trace as pt_mod, trace_reduce as tr
    from repro import trace

    lanes = sess.cell.config["engine"]["max_batch"]
    trace_dir = Path(tempfile.mkdtemp(prefix="chipbench-spans-"))
    try:
        with trace.record() as rec:
            run = harness.measure(sess, seed, seconds, t_start=t_start, trace_dir=trace_dir)
        xplane = harness._xplane(trace_dir)
        rows = pt_mod.load_xplane(xplane)
        if keep_trace is not None:
            keep_trace.mkdir(parents=True, exist_ok=True)
            tr.save_rows(last_steps(rows, 12), keep_trace / "rows.json.gz")
            (keep_trace / "lines.json").write_text(json.dumps(catalog(xplane), indent=1))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    pt = pt_mod.reduce(rows)
    run.trace = pt.summary
    metrics = {}
    for m in sess.cell.per_layer:
        v = harness.load_reader(sess.cell.bench, m["name"])(run)
        metrics[m["name"]] = None if v is None else float(v)
    pool = pt_mod.kvpool_device_ms(pt, lanes)
    program = pt_mod.decode_program_ms(pt, lanes)
    idle = pt_mod.idle_by_span(pt, lanes)
    dev = sess.dev
    return {
        "workload": sess.cell.name, "seed": seed,
        "device": {"kind": dev.device_kind, "count": sess.n_devices,
                   "memory_peak_bytes": int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))},
        "setup": {k: round(v, 3) for k, v in sess.phases.items()},
        "metrics": metrics,
        "kvpool_device_ms": pool, "decode_program_ms": program,
        "dispatch_idle_pct": pt_mod.dispatch_idle_pct(pt),
        "decode_steps_traced": len(pt.decode_steps(lanes)),
        "idle_ms_by_span": idle,
        "accounted_ms": None if pool is None or program is None
        else pool + program + sum(idle.values()),
        "idle_gaps": pt_mod.idle_gaps(pt), "idle_gaps_harness": tr.idle_gaps(pt.summary),
        "program_ms": dict(list(pt_mod.program_ms(pt, lanes).items())[:25]),
        "device_ops": tr.top_ops(pt.summary, 15),
        "host_spans": sorted({s.name for s in pt.host}),
        "self_ms": self_ms(rec, run, lanes),
    }


def main(argv=None) -> int:
    from chipbench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-trace", type=Path, default=None)
    args = ap.parse_args(argv)
    sess = harness.prepare(args.workload, args.seed, t_start=T_START)
    print(json.dumps(measure(sess, args.seed, args.seconds, t_start=T_START,
                             keep_trace=args.keep_trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
