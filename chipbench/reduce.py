"""Arithmetic the metric readers share: percentiles over requests, token
gaps, time to first token, decode periods, work per step."""
from __future__ import annotations

import math
import statistics

from . import trace_reduce


def percentile(values, p: float) -> float:
    """Nearest-rank percentile (the smallest value with at least ``p``% of
    the values at or below it).  ``inf`` marks a miss and sorts last."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return xs[max(math.ceil(p / 100 * len(xs)) - 1, 0)]


def tokens_in_window(run) -> int:
    return sum(run.t_open <= t < run.t_close for r in run.requests for t in r.times)


def token_gaps(run) -> list[float]:
    """Seconds between consecutive tokens of one request, both in the window."""
    out = []
    for r in run.requests:
        ts = [t for t in r.times if run.t_open <= t < run.t_close]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def ttfts(run) -> list[float]:
    """Seconds from due time to first token, for every request due in the
    window.  One that never got a token (failed, or still waiting when the
    harness stopped) is a miss: ``inf``."""
    out = []
    for r in run.requests:
        if run.t_open <= r.due < run.t_close:
            bad = r.request.state == "starved" or r.request.retries
            out.append(r.times[0] - r.due if r.times and not bad else math.inf)
    return out


def decode_periods(run) -> list[float]:
    """Seconds between consecutive decodes at the full lane width, in the window."""
    at = [(t, w) for s in run.steps for t, w, _ in s.decodes if run.t_open <= t < run.t_close]
    return [b[0] - a[0] for a, b in zip(at, at[1:]) if a[1] == b[1] == run.lanes]


def median(xs) -> float | None:
    return statistics.median(xs) if xs else None


def roofline_pct(run) -> float | None:
    """Least time of the traced steps' matrix products over the device time
    of every op that computes them."""
    steps = run.traced_steps()
    dev = trace_reduce.matmul_seconds(run.trace) if run.trace else 0.0
    if not steps or dev <= 0:
        return None
    return 100.0 * sum(run.step_work(s)[2] for s in steps) / dev


def idle_pct(run) -> float | None:
    share = trace_reduce.idle_share(run.trace) if run.trace else None
    return None if share is None else 100.0 * share
