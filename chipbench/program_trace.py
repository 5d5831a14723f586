"""The program's own spans and program names in a profiler trace.

``trace_reduce`` reads the device ops and the harness's two host spans.  A
trace taken while the program records its spans (``repro.trace.record()``
inside the profiler session) also holds, on the same clock:

- the program's host spans, ``serve.*`` (engine phases) and ``kvpool.*``
  (the KV pool's dense-view copies and block hashing), each with its
  attributes (``serve.decode`` carries ``width``);
- one event per executed program on the device plane's ``XLA Modules`` line,
  named ``jit_<function>(<fingerprint>)`` on a TPU v5e.  The
  engine's step programs are ``jit_serve_decode_w<width>``,
  ``jit_serve_chunk_w<width>`` and ``jit_serve_prefill_<len>``; everything
  else the device runs between them is eager work: the pool's gather,
  scatter and scratch re-zeroing, and the argmax of the next token.

From these this module reads the device ms a step spends in the pool, the
device ms of one decode program, and the device's idle time by the innermost
host span the host was in.  ``chipbench/spans.py`` runs a cell with the
program's spans on and prints them.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
import statistics
from pathlib import Path

from . import trace_reduce as tr

MODULES_LINE = "XLA Modules"
PROGRAM_SPANS = ("serve.", "kvpool.")
STEP_PROGRAM = "jit_serve_"
DISPATCH_SPANS = ("kvpool.", "serve.launch")
_FINGERPRINT = re.compile(r"\(\d+\)$")


def load_xplane(path: str | Path) -> list[list]:
    """What :func:`trace_reduce.load_xplane` keeps, plus the program's host
    spans and the device plane's ``XLA Modules`` events."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    rows = []
    for plane in data.planes:
        device = bool(tr.DEVICE_PLANE.match(plane.name))
        host = plane.name.startswith("/host:")
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name not in (tr.OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if host and not (ev.name in tr.HOST_SPANS or ev.name.startswith(PROGRAM_SPANS)):
                    continue
                stats = {k: v for k, v in ev.stats if isinstance(v, (int, float, str))}
                rows.append([plane.name, line.name, ev.name, float(ev.start_ns),
                             float(ev.duration_ns), stats])
    return rows


@dataclasses.dataclass(frozen=True)
class HostSpan:
    name: str
    start: float  # ns, the trace's clock
    end: float
    attrs: dict


@dataclasses.dataclass(frozen=True)
class Module:
    name: str  # jit_<function>, fingerprint dropped
    start: float
    end: float


@dataclasses.dataclass
class ProgramTrace:
    summary: tr.TraceSummary  # what the benchmark's readers read, unchanged
    host: list[HostSpan]  # harness and program spans, sorted by start
    modules: list[Module]  # executed programs, sorted by start

    def __post_init__(self):
        self._op_starts = [o.start for o in self.summary.ops]
        self._host_starts = [s.start for s in self.host]
        self._module_starts = [m.start for m in self.modules]
        self._busy = tr.union([(o.start, o.end) for o in self.summary.ops])
        self._busy_ends = [b for _, b in self._busy]

    def idle(self, lo: float, hi: float) -> list[tuple[float, float]]:
        """The gaps between device ops inside ``[lo, hi)``."""
        busy = self._busy[bisect.bisect_right(self._busy_ends, lo):]
        out, t = [], lo
        for a, b in busy:
            if a >= hi:
                break
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if t < hi:
            out.append((t, hi))
        return out

    def ops_in(self, lo: float, hi: float) -> list[tr.Op]:
        """Device ops (loop containers left out) that start in ``[lo, hi)``."""
        ops = self.summary.ops[bisect.bisect_left(self._op_starts, lo):
                               bisect.bisect_left(self._op_starts, hi)]
        return [o for o in ops if not o.container]

    def program_of(self, t: float) -> str | None:
        """The program executing on the device at time ``t`` (programs run
        one after another, so at most one holds it)."""
        i = bisect.bisect_right(self._module_starts, t) - 1
        return self.modules[i].name if i >= 0 and t < self.modules[i].end else None

    def innermost(self, t: float) -> str:
        """The innermost host span (program's or harness's) open at ``t``:
        spans of one thread nest, so it is the latest to start of those open."""
        for i in range(bisect.bisect_right(self._host_starts, t) - 1, -1, -1):
            if t < self.host[i].end:
                return self.host[i].name
        return "harness"

    def decode_steps(self, width: int) -> list[tr.Span]:
        """The harness's step spans that ran a decode at ``width`` lanes."""
        decodes = [s for s in self.host
                   if s.name == "serve.decode" and s.attrs.get("width") == width]
        return [st for st in self.summary.steps()
                if any(st.start <= d.start < st.end for d in decodes)]


def reduce(rows: list[list]) -> ProgramTrace:
    ops = [r for r in rows if r[1] != MODULES_LINE]
    host = sorted((HostSpan(r[2], r[3], r[3] + r[4], r[5]) for r in rows
                   if r[0].startswith("/host:")), key=lambda s: s.start)
    modules = sorted((Module(_FINGERPRINT.sub("", r[2]), r[3], r[3] + r[4]) for r in rows
                      if r[1] == MODULES_LINE), key=lambda m: m.start)
    return ProgramTrace(tr.summarize(ops), host, modules)


def _median(xs):
    return statistics.median(xs) if xs else None


def kvpool_device_ms(pt: ProgramTrace, width: int) -> float | None:
    """Median over the steps that decode at ``width`` of the device ms of ops
    outside the engine's step programs."""
    if not pt.modules:
        return None
    return _median([sum(o.end - o.start for o in pt.ops_in(st.start, st.end)
                        if not (pt.program_of(o.start) or "").startswith(STEP_PROGRAM)) / 1e6
                    for st in pt.decode_steps(width)])


def decode_program_ms(pt: ProgramTrace, width: int) -> float | None:
    """Median device ms of one ``jit_serve_decode_w<width>`` execution."""
    name = f"{STEP_PROGRAM}decode_w{width}"
    rng = pt.summary.range()
    return _median([(m.end - m.start) / 1e6 for m in pt.modules
                    if m.name == name and rng and rng[0] <= m.start < rng[1]])


def dispatch_idle_pct(pt: ProgramTrace) -> float | None:
    """Share of the time inside the harness's step spans in which the device
    is idle while the host is inside a ``kvpool.*`` or ``serve.launch`` span."""
    steps = [(s.start, s.end) for s in pt.summary.steps()]
    if not steps:
        return None
    idle = [g for a, b in tr.union(steps) for g in pt.idle(a, b)]
    dispatch = tr.union([(s.start, s.end) for s in pt.host if s.name.startswith(DISPATCH_SPANS)])
    return 100.0 * tr.covered(idle, dispatch) / sum(b - a for a, b in tr.union(steps))


def idle_by_span(pt: ProgramTrace, width: int) -> dict[str, float]:
    """Mean idle ms per step that decodes at ``width``, by the innermost
    host span open at each moment of idleness."""
    steps = pt.decode_steps(width)
    out: dict[str, float] = {}
    for st in steps:
        cuts = sorted({t for s in pt.host if s.end > st.start and s.start < st.end
                       for t in (s.start, s.end)})
        for a, b in pt.idle(st.start, st.end):
            edges = [a] + [t for t in cuts if a < t < b] + [b]
            for x, y in zip(edges, edges[1:]):
                name = pt.innermost((x + y) / 2)
                out[name] = out.get(name, 0.0) + (y - x) / 1e6 / len(steps)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_gaps(pt: ProgramTrace, k: int = 10) -> list[list]:
    """:func:`trace_reduce.idle_gaps`, each gap named by the innermost span
    the host was in when it began."""
    rng = pt.summary.range()
    if rng is None:
        return []
    gaps = sorted(pt.idle(*rng), key=lambda g: g[0] - g[1])[:k]
    return [[pt.innermost(a), (b - a) / 1e9] for a, b in gaps]


def program_ms(pt: ProgramTrace, width: int) -> dict[str, float]:
    """Mean device ms per step that decodes at ``width``, by program."""
    steps = pt.decode_steps(width)
    out: dict[str, float] = {}
    for st in steps:
        for o in pt.ops_in(st.start, st.end):
            name = pt.program_of(o.start) or "(no program)"
            out[name] = out.get(name, 0.0) + (o.end - o.start) / 1e6 / len(steps)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
