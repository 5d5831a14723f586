"""From a profiler trace to device busy time, idle gaps and matmul time.

A trace is first flattened to events ``[plane, line, name, start_ns,
dur_ns, stats]`` (:func:`load_xplane`); everything below works on that list,
so the tests can feed it a small recorded trace (``tests/data``).

- Device ops are the events on a device plane's ``XLA Ops`` line.  On a
  TPU each event is named by its HLO instruction's text
  (``%name = shape opcode(operands), ...``).  Loop containers (``while``,
  ``conditional``, ``call``) enclose their body's ops on the same line; they
  count toward busy time (a union) but not as ops of their own.
- Host spans are the harness's own ``TraceAnnotation`` events on a host
  plane (``engine.step`` with its ``step_id``, ``harness.idle``).
- An op computes matrix products when it is a Pallas kernel
  (``custom_call_target="tpu_custom_call"``; every Pallas kernel of the
  program is a matmul), an XLA ``convolution`` or ``dot``, or an output
  fusion (``kind=kOutput``, rooted at a dot).  The data movement that made
  one of its operands right before it (a ``dynamic-slice`` of a layer's
  weight out of the stacked parameters into VMEM, a ``pad`` of an unaligned
  weight, a ``copy``) is part of that product's time: on a TPU the slice is
  where the weight is read from HBM.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
OPS_LINE = "XLA Ops"
STEP_SPAN = "engine.step"
IDLE_SPAN = "harness.idle"
HOST_SPANS = (STEP_SPAN, IDLE_SPAN)
CONTAINERS = ("while", "conditional", "call")
MOVES = ("dynamic-slice", "pad", "copy", "slice", "transpose", "concatenate", "bitcast")
_NAME = re.compile(r"^%([\w.-]+) = ")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")


@dataclasses.dataclass(frozen=True)
class Op:
    start: float  # ns
    end: float
    name: str
    matmul: bool  # computes a matrix product, or moved an operand into one
    container: bool = False  # a loop that encloses other ops


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    step_id: int | None


@dataclasses.dataclass
class TraceSummary:
    ops: list[Op]  # device ops, sorted by start
    spans: list[Span]  # harness spans, sorted by start
    n_devices: int

    def steps(self) -> list[Span]:
        return [s for s in self.spans if s.name == STEP_SPAN]

    def range(self) -> tuple[float, float] | None:
        """From the first step span's start to the last one's end."""
        st = self.steps()
        return (st[0].start, st[-1].end) if st else None


def load_xplane(path: str | Path) -> list[list]:
    """Flatten the profiler's ``.xplane.pb`` to event rows (device ops and
    the harness's host spans only)."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    rows = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if host and ev.name not in HOST_SPANS:
                    continue
                stats = {k: v for k, v in ev.stats if isinstance(v, (int, float, str))}
                rows.append([plane.name, line.name, ev.name, float(ev.start_ns),
                             float(ev.duration_ns), stats])
    return rows


def save_rows(rows: list[list], path: str | Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(rows, f)


def load_rows(path: str | Path) -> list[list]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def parse_op(text: str) -> tuple[str, str, list[str]]:
    """(instruction name, opcode, operand names) of an HLO op event; an event
    that is not HLO text is its own name with no opcode."""
    m = _NAME.match(text)
    if not m:
        return text, "", []
    rest = text[m.end():]
    op = _OPCODE.search(rest)
    if not op:
        return m.group(1), "", []
    args = rest[op.end():]
    depth, end = 1, 0
    for end, ch in enumerate(args):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            break
    return m.group(1), op.group(1), re.findall(r"%([\w.-]+)", args[:end])


def is_matmul(text: str, opcode: str) -> bool:
    if opcode == "custom-call":
        return 'custom_call_target="tpu_custom_call"' in text
    return opcode in ("convolution", "dot") or (opcode == "fusion" and "kind=kOutput" in text)


def label(text: str, name: str, opcode: str) -> str:
    base = re.sub(r"\.\d+$", "", name)
    return f"pallas:{base}" if opcode == "custom-call" and "tpu_custom_call" in text else base


def summarize(rows: list[list]) -> TraceSummary:
    ops, spans, devices = [], [], set()
    last: dict[str, int] = {}  # instruction name -> index of its latest op
    for plane, line, text, start, dur, stats in sorted(rows, key=lambda r: r[3]):
        if DEVICE_PLANE.match(plane):
            devices.add(plane)
            name, opcode, operands = parse_op(text)
            if opcode in CONTAINERS:
                ops.append(Op(start, start + dur, label(text, name, opcode), False, True))
                continue
            mm = is_matmul(text, opcode)
            if mm:
                for arg in operands:
                    j = last.pop(arg, None)
                    if j is not None and ops[j].name.startswith(MOVES) and not ops[j].matmul:
                        ops[j] = dataclasses.replace(ops[j], matmul=True)
            last[name] = len(ops)
            ops.append(Op(start, start + dur, label(text, name, opcode), mm))
        elif text in HOST_SPANS:
            sid = stats.get("step_id")
            spans.append(Span(text, start, start + dur, int(sid) if sid is not None else None))
    ops.sort(key=lambda o: o.start)
    spans.sort(key=lambda s: s.start)
    return TraceSummary(ops, spans, max(len(devices), 1))


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def covered(intervals, windows) -> float:
    """Length of the (already merged) ``intervals`` that lies inside the
    merged ``windows``."""
    windows = union(windows)
    total, j = 0.0, 0
    for a, b in intervals:
        while j < len(windows) and windows[j][1] <= a:
            j += 1
        k = j
        while k < len(windows) and windows[k][0] < b:
            total += min(b, windows[k][1]) - max(a, windows[k][0])
            k += 1
    return total


def busy(ts: TraceSummary) -> tuple[float, float]:
    """Device busy seconds (union of op intervals, averaged over devices) and
    the traced window's seconds, over the range of the step spans."""
    rng = ts.range()
    if rng is None:
        return 0.0, 0.0
    merged = union(clip([(o.start, o.end) for o in ts.ops], *rng))
    return sum(b - a for a, b in merged) / 1e9 / ts.n_devices, (rng[1] - rng[0]) / 1e9


def idle_share(ts: TraceSummary) -> float | None:
    """1 - (device busy inside step spans) / (time inside step spans)."""
    steps = [(s.start, s.end) for s in ts.steps()]
    if not steps:
        return None
    merged = union([(o.start, o.end) for o in ts.ops])
    inside = covered(merged, steps) / ts.n_devices
    span = sum(b - a for a, b in union(steps))
    return 1.0 - inside / span


def matmul_seconds(ts: TraceSummary) -> float:
    """Device seconds of matrix-product ops that start in the step range."""
    rng = ts.range()
    if rng is None:
        return 0.0
    return sum(o.end - o.start for o in ts.ops
               if o.matmul and not o.container and rng[0] <= o.start < rng[1]) / 1e9


def top_ops(ts: TraceSummary, k: int = 10) -> list[list]:
    rng = ts.range()
    tot: dict[str, float] = {}
    for o in ts.ops:
        if rng and rng[0] <= o.start < rng[1] and not o.container:
            tot[o.name] = tot.get(o.name, 0.0) + (o.end - o.start) / 1e9
    return [[n, s] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(ts: TraceSummary, k: int = 10) -> list[list]:
    """The longest gaps between device ops in the step range, each named by
    the harness span the host was in when the gap began."""
    rng = ts.range()
    if rng is None:
        return []
    merged = union(clip([(o.start, o.end) for o in ts.ops], *rng))
    edges = [rng[0]] + [x for ab in merged for x in ab] + [rng[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        host = next((s.name for s in ts.spans if s.start <= a < s.end), "harness")
        out.append([host, (b - a) / 1e9])
    return out
