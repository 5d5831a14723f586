"""One benchmark run: set-up, the measured window, the metrics, the check.

Everything a run needs is found by name from ``BENCHMARK.json``:

- the workload entry names its configuration (``configs[].file``) and its
  traffic mix (``chipbench/traffic/<traffic>.json``);
- the configuration file names the program's registry entry, the engine
  sizing and its plain reference (``chipbench/references/<reference>.py``);
- each metric is read by ``chipbench/metrics/<name>.py`` (``read(run)``);
- the limits of the correctness check are in ``chipbench/limits/<workload>.json``;
- the chip's peaks are in ``chipbench/peaks.json``, keyed by ``device_kind``.

The window drives the system's normal serving path: ``repro.tune`` for the
detected device, ``bundle.runtime(strict=True)``, ``rt.serve`` over bfloat16
weights that this harness makes on the device from the seed, then
``engine.submit`` when each request is due and ``engine.step`` in a loop.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from . import check, trace_reduce, traffic as traffic_mod
from .work import Shapes, chunk_work, decode_work, least_time

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = "chipbench"
WARM_SEED = 0x5EED
TRACE_SECONDS = 8.0  # the traced part of a --trace 1 window: its last seconds


class HarnessError(RuntimeError):
    """The run cannot be made as asked; no result is printed."""


clock = time.perf_counter


# -- what BENCHMARK.json names -------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    root: Path
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def bench(self) -> Path:
        return self.root / BENCH_DIR


def _json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise HarnessError(f"missing {path}") from None


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise HarnessError(f"no workload {name!r} in BENCHMARK.json (known: {sorted(work)})")
    w = work[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m else m["moves"] in reported)]
    bench = root / BENCH_DIR
    return Cell(name, root, _json(root / cfg["file"]),
                _json(bench / "traffic" / f"{w['traffic']}.json"),
                _json(bench / "limits" / f"{name}.json"), e2e, per_layer)


def load_peaks(kind: str, root: Path = ROOT) -> dict:
    table = _json(root / BENCH_DIR / "peaks.json")["devices"]
    if kind not in table:
        raise HarnessError(f"device kind {kind!r} is not in peaks.json (known: {sorted(table)})")
    return table[kind]


def load_reader(bench: Path, metric: str):
    path = bench / "metrics" / f"{metric}.py"
    if not path.exists():
        raise HarnessError(f"no reader for metric {metric!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- what a run records ----------------------------------------------------------
@dataclasses.dataclass
class ReqRec:
    due: float  # clock time the request was due
    submitted: float
    prompt_len: int
    request: object  # the engine's Request (host data only)
    times: list[float] = dataclasses.field(default_factory=list)  # token landings


@dataclasses.dataclass
class StepRec:
    step_id: int
    start: float
    end: float = 0.0
    decodes: list = dataclasses.field(default_factory=list)  # (clock, width, contexts)
    chunks: list = dataclasses.field(default_factory=list)  # (width, start, n_real, final)
    snaps: list = dataclasses.field(default_factory=list)

    @property
    def has_chunk(self) -> bool:
        return bool(self.chunks)


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read."""

    cell: Cell
    shapes: Shapes
    peaks: dict
    lanes: int
    seconds: float
    setup_s: float
    t_open: float
    t_close: float
    requests: list[ReqRec]
    steps: list[StepRec]
    compile_times: list[float]
    trace: trace_reduce.TraceSummary | None = None

    def window_steps(self) -> list[StepRec]:
        return [s for s in self.steps if self.t_open <= s.end < self.t_close]

    def step_work(self, s: StepRec) -> tuple[float, float, float]:
        """(flops, bytes, least seconds) of one step's programs."""
        flops = nbytes = least = 0.0
        for _, _, ctx in s.decodes:
            f, b = decode_work(self.shapes, ctx)
            flops, nbytes, least = flops + f, nbytes + b, least + least_time(f, b, self.peaks)
        for _, start, n, final in s.chunks:
            f, b = chunk_work(self.shapes, start, n, final)
            flops, nbytes, least = flops + f, nbytes + b, least + least_time(f, b, self.peaks)
        return flops, nbytes, least

    def traced_steps(self) -> list[StepRec]:
        """Host records of the steps whose spans the trace holds whole."""
        if self.trace is None:
            return []
        ids = {s.step_id for s in self.trace.steps()}
        return [s for s in self.steps if s.step_id in ids]


class Recorder:
    """Engine hooks: what each step's programs covered."""

    def __init__(self):
        self.engine = None
        self.current: StepRec | None = None

    def on_decode(self, width: int) -> None:
        if self.current is None:
            return
        e = self.engine
        ctx = [int(e.positions[i]) + 1 for i, r in enumerate(e.slots)
               if r is not None and r.state == "active"]
        self.current.decodes.append((clock(), width, ctx))

    def on_prefill(self, width: int) -> None:
        # Called after the chunk ran and before the engine advances the lane's
        # chunk position: the lane whose position moves by the next snapshot
        # is the one this chunk covered, from its position here.
        if self.current is None:
            return
        snap = {id(r): (r, r._chunk_pos, len(r._chunk_tokens))
                for r in self.engine.slots if r is not None and r.state == "prefilling"}
        self.current.snaps.append((width, snap))

    def close_step(self) -> None:
        s, self.current = self.current, None
        seen = {k: v for _, snap in s.snaps for k, v in snap.items()}
        ends = {k: r._chunk_pos for k, (r, _, _) in seen.items()}
        for j, (width, snap) in enumerate(s.snaps):
            after = {k: v[1] for k, v in s.snaps[j + 1][1].items()} if j + 1 < len(s.snaps) else ends
            for k, (r, pos, total) in snap.items():
                if after.get(k, pos) != pos:
                    end = after[k]
                    s.chunks.append((width, pos, end - pos, end >= total))
                    break
        s.snaps = []


# -- set-up -------------------------------------------------------------------------
def arch_config(config: dict):
    from repro.configs import registry

    c = config["config"]
    if c.get("partial_rotary_factor", 1.0) != 1.0 or c.get("rope_scaling") is not None:
        raise HarnessError("the program runs full plain RoPE only")
    return dataclasses.replace(
        registry.get(config["registry_arch"]),
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        norm_eps=c["rms_norm_eps"], rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"],
    )


def seed_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), (seed >> 32) & 0xFFFFFFFF)


def make_params(model, seed: int):
    """Weights in the program's parameter layout, made on the device from
    the seed in one jitted call, in the type they are served in."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(model.init, jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, sd) in enumerate(leaves):
            name = getattr(path[-1], "key", str(path[-1]))
            k = jax.random.fold_in(key, i)
            if name in ("ln1", "ln2", "final_norm"):
                out.append(jnp.ones(sd.shape, sd.dtype))
            elif name in ("embed", "unembed"):
                out.append(jax.random.normal(k, sd.shape, sd.dtype) * jnp.asarray(0.02, sd.dtype))
            elif name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
                scale = math.sqrt(2.0 / (sd.shape[-2] + sd.shape[-1]))
                out.append(jax.random.normal(k, sd.shape, sd.dtype) * jnp.asarray(scale, sd.dtype))
            else:
                raise HarnessError(f"no rule to make parameter {jax.tree_util.keystr(path)}")
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(build)(seed_key(seed)))


def ladder(engine_cfg: dict) -> list[int]:
    """Chunk widths the engine can run: its default bucket ladder, doubled
    below ``cache_len``, up to the chunk cap."""
    rungs = [32, 64, 128]
    while rungs[-1] * 2 < engine_cfg["cache_len"]:
        rungs.append(rungs[-1] * 2)
    return [r for r in rungs if r <= engine_cfg["prefill_chunk_tokens"]]


def warm_up(engine, engine_cfg: dict, vocab: int) -> None:
    """Run every program shape the window can use, through the public API:
    each chunk width and each decode width."""
    rng = np.random.default_rng(WARM_SEED)
    toks = lambda n: rng.integers(0, vocab, n, dtype=np.int32)
    for w in ladder(engine_cfg):
        engine.submit(toks(w), max_new_tokens=2)
        engine.drain()
    width = 1
    while True:
        for _ in range(width):
            engine.submit(toks(32), max_new_tokens=3)
        engine.drain()
        if width >= engine_cfg["max_batch"]:
            break
        width = min(width * 2, engine_cfg["max_batch"])


# -- the window ------------------------------------------------------------------------
def _annotate(on: bool, name: str, **kw):
    if not on:
        import contextlib

        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name, **kw)


def serve_window(engine, rec: Recorder, arrivals, tr: dict, seconds: float, trace_dir) -> dict:
    """Open loop: submit each request when due, step while work is pending,
    until the window closes."""
    import jax

    lead = float(tr["lead_in_s"])
    t0 = clock()
    t_open, t_close = t0 + lead, t0 + lead + seconds
    reqs: list[ReqRec] = []
    live: list[ReqRec] = []
    steps: list[StepRec] = []
    i, tracing, traced = 0, False, False
    while True:
        now = clock()
        while i < len(arrivals) and t0 + arrivals[i].due <= now:
            a = arrivals[i]
            r = ReqRec(t0 + a.due, now, len(a.prompt),
                       engine.submit(a.prompt, max_new_tokens=a.max_new).request)
            reqs.append(r)
            live.append(r)
            i += 1
        if trace_dir is not None and not traced and now >= t_close - min(TRACE_SECONDS, seconds):
            jax.profiler.start_trace(str(trace_dir))
            tracing = traced = True
        if now >= t_close:
            if tracing:
                jax.profiler.stop_trace()
            break
        if engine.pending():
            s = StepRec(len(steps), clock())
            rec.current = s
            with _annotate(tracing, trace_reduce.STEP_SPAN, step_id=s.step_id):
                engine.step()
            s.end = clock()
            rec.close_step()
            steps.append(s)
            still = []
            for r in live:
                n = len(r.request.output)
                if n > len(r.times):
                    r.times.extend([s.end] * (n - len(r.times)))
                if not r.request.done:
                    still.append(r)
            live = still
        else:
            nxt = t0 + arrivals[i].due if i < len(arrivals) else math.inf
            until = min(nxt, t_open if now < t_open else t_close)
            with _annotate(tracing, trace_reduce.IDLE_SPAN):
                time.sleep(max(0.0, min(until - clock(), 0.05)))
    return {"requests": reqs, "steps": steps, "t_open": t_open, "t_close": t_close}


# -- the run -----------------------------------------------------------------------------
@dataclasses.dataclass
class Session:
    """A set-up engine and what made it; measure() serves windows on it."""

    cell: Cell
    dev: object
    n_devices: int
    peaks: dict
    rt: object
    cfg: object
    params: object
    engine: object
    rec: Recorder
    phases: dict
    compile_times: list[float]


def prepare(name: str, seed: int, *, t_start: float, root: Path = ROOT,
            require_chip: bool = True, tune_device: str | None = None) -> Session:
    """Everything before the window except the lead-in: the chip check, the
    compile cache, tune, runtime, weights, engine and warm-up."""
    cell = load_cell(name, root)
    import jax

    devs = jax.devices()
    dev = devs[0]
    if require_chip and dev.platform != "tpu":
        raise HarnessError(f"no TPU: JAX runs on {dev.platform!r}")
    peaks = load_peaks(dev.device_kind if require_chip else "TPU v5 lite", root)
    if require_chip:
        import os

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    compile_times: list[float] = []
    from jax import monitoring

    def on_event(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_times.append(clock())

    monitoring.register_event_duration_secs_listener(on_event)

    import repro
    from repro.core.devices import detect_device
    from repro.models.model import build_model

    phases = {"import": clock() - t_start}
    t = clock()
    tune_as = tune_device or detect_device()
    bundle = repro.tune([cell.config["registry_arch"]], devices=(tune_as,), seed=0)
    rt = bundle.runtime(device=tune_as, strict=True)
    phases["tune"] = clock() - t

    t = clock()
    cfg = arch_config(cell.config)
    model = build_model(cfg)
    params = make_params(model, seed)
    phases["weights"] = clock() - t

    t = clock()
    ecfg = cell.config["engine"]
    rec = Recorder()
    engine = rt.serve(model, params, on_decode=rec.on_decode, on_prefill=rec.on_prefill, **ecfg)
    rec.engine = engine
    phases["engine"] = clock() - t

    t = clock()
    warm_up(engine, ecfg, cfg.vocab)
    phases["warm_up"] = clock() - t
    return Session(cell, dev, len(devs), peaks, rt, cfg, params, engine, rec, phases,
                   compile_times)


def measure(sess: Session, seed: int, seconds: float, *, t_start: float,
            trace_dir: Path | None = None, traffic: dict | None = None) -> RunRecord:
    """Lead-in and one measured window of the cell's traffic (or ``traffic``)."""
    tr = traffic or sess.cell.traffic
    horizon = tr["lead_in_s"] + seconds
    arrivals = traffic_mod.schedule(tr, seed, horizon, sess.cfg.vocab)
    win = serve_window(sess.engine, sess.rec, arrivals, tr, seconds, trace_dir)
    sess.phases["lead_in"] = float(tr["lead_in_s"])
    return RunRecord(
        cell=sess.cell, shapes=Shapes.from_config(sess.cell.config["config"]),
        peaks=sess.peaks, lanes=sess.cell.config["engine"]["max_batch"], seconds=seconds,
        setup_s=win["t_open"] - t_start, t_open=win["t_open"], t_close=win["t_close"],
        requests=win["requests"], steps=win["steps"], compile_times=sess.compile_times,
    )


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, t_start: float,
             root: Path = ROOT, require_chip: bool = True, tune_device: str | None = None,
             control: bool = False, keep_trace: Path | None = None) -> dict:
    """Make one run of workload ``name``; returns the result's fields."""
    sess = prepare(name, seed, t_start=t_start, root=root, require_chip=require_chip,
                   tune_device=tune_device)
    cell, rt, dev, phases, params = sess.cell, sess.rt, sess.dev, sess.phases, sess.params
    compile_times = sess.compile_times
    trace_dir = Path(tempfile.mkdtemp(prefix="chipbench-trace-")) if trace else None
    incidents0 = rt.incident_count()
    record = measure(sess, seed, seconds, t_start=t_start, trace_dir=trace_dir)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind, "count": sess.n_devices,
                      "memory_peak_bytes": int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))}}
    incidents = rt.incident_count() - incidents0
    # Free the program's state before the reference runs: the pool and the
    # compiled programs' buffers go with the engine.
    sess.rec.engine = None
    sess.engine = sess.rec = None
    gc.collect()

    if trace_dir is not None:
        rows = trace_reduce.load_xplane(_xplane(trace_dir))
        if keep_trace is not None:
            keep_trace.mkdir(parents=True, exist_ok=True)
            trace_reduce.save_rows(rows, keep_trace / "rows.json.gz")
        record.trace = trace_reduce.summarize(rows)
        busy_s, window_s = trace_reduce.busy(record.trace)
        out["device"].update(busy_s=busy_s, window_s=window_s)
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(record.trace),
                            "idle_gaps": trace_reduce.idle_gaps(record.trace)}
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_reader(cell.bench, m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    window = [r for r in record.requests if record.t_open <= r.due < record.t_close]
    failed = sum(1 for r in window if r.request.state == "starved" or r.request.retries)
    t = clock()
    verdict = correctness(record, params, seed, control)
    phases["check_after_window"] = clock() - t
    late = [r.submitted - r.due for r in window]
    out.update(
        correct=verdict["correct"], attempted=len(window), failed=failed, metrics=metrics,
        setup={k: round(v, 3) for k, v in phases.items()},
        notes={"dispatch_incidents": incidents, "compiles_in_window": sum(
            record.t_open <= c < record.t_close for c in compile_times),
            "generator_late_ms_max": 1e3 * max(late, default=0.0)},
    )
    if control:
        out["control"] = verdict["control"]
    out["checks"] = verdict["checks"]
    return out


def _xplane(trace_dir: Path) -> Path:
    found = sorted(trace_dir.rglob("*.xplane.pb"))
    if not found:
        raise HarnessError(f"the profiler wrote no trace under {trace_dir}")
    return found[-1]


def correctness(record: RunRecord, params, seed: int, control: bool) -> dict:
    """Served tokens of a seeded sample of finished requests against the
    configuration's plain reference (see ``check.py``).  With ``control``,
    the fp8 control's tokens stand in for the served ones."""
    cell = record.cell
    lim, tr = cell.limits, cell.traffic["check"]
    finished = [check.Served(np.asarray(r.request.prompt), list(r.request.output))
                for r in record.requests if r.request.done]
    picked = check.sample(finished, seed, tr["min_compared_tokens"], tr["max_requests"])
    ref = check.load_reference(cell.config["reference"])
    prog, ctrl = check.gaps(ref, params, cell.config["config"], picked,
                            traffic_mod.longest_request(cell.traffic), control=control)
    gap = check.widest(ctrl if control else prog)
    compared = int(sum(len(g) for g in prog))
    truncated = int(sum(r.request.truncated_tokens for r in record.requests))
    checks = {
        "max_logit_gap": {"value": gap, "limit": lim["max_logit_gap"]},
        "compared_tokens": {"value": compared, "limit": tr["min_compared_tokens"]},
        "truncated_tokens": {"value": truncated, "limit": 0},
    }
    ok = (gap <= lim["max_logit_gap"] and compared >= tr["min_compared_tokens"]
          and truncated == 0)
    out = {"correct": bool(ok), "checks": checks}
    if control:
        out["control"] = {"program_max_logit_gap": check.widest(prog), "requests": len(picked)}
    return out
