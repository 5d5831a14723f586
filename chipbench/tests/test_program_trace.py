"""The program's spans and program names in a trace (``program_trace``), on a
hand-made trace and a small recorded one, and a window with the spans on
(``spans.py``) on the CPU."""
from pathlib import Path

import pytest

from chipbench import program_trace as pt
from chipbench import trace_reduce as tr
from conftest import TINY, tiny_run

DATA = Path(__file__).resolve().parent / "data"
DEV, HOST, MS = "/device:TPU:0", "/host:CPU", 1e6


def host(name, a, b, **attrs):
    return [HOST, "python3", name, a * MS, (b - a) * MS, attrs]


def op(text, a, b):
    return [DEV, "XLA Ops", text, a * MS, (b - a) * MS, {}]


def module(name, a, b):
    return [DEV, pt.MODULES_LINE, name, a * MS, (b - a) * MS, {}]


def harness_rows():
    # step 0 decodes at the full width 4 in [0, 100) ms; step 1 at width 2 in [150, 300)
    return [
        host("engine.step", 0, 100, step_id=0),
        host("harness.idle", 100, 150),
        host("engine.step", 150, 300, step_id=1),
        op("%gather.1 = x gather(%p, %i)", 10, 15),
        op("%while.2 = x while(%t), body=%b", 22, 50),
        op("%fusion.3 = x fusion(%w), kind=kLoop", 22, 30),
        op('%mm_bm8_bn512_bk512_mnk.4 = x custom-call(%fusion.3, %w), '
           'custom_call_target="tpu_custom_call"', 30, 50),
        op("%reduce.5 = x reduce(%l), kind=kLoop", 70, 75),
        op("%fusion.6 = x fusion(%x), kind=kLoop", 160, 200),
    ]


def program_rows():
    return [
        host("serve.step", 2, 98, step=0),
        host("serve.decode", 3, 90, width=4, rows=4),
        host("serve.launch", 4, 40, site="engine.decode", width=4),
        host("kvpool.gather", 5, 20, lanes=4),
        host("serve.sample", 60, 85, width=4),
        host("serve.step", 152, 295, step=1),
        host("serve.decode", 155, 290, width=2, rows=2),
        module("jit_gather(11)", 10, 15),
        module("jit_serve_decode_w4(12)", 22, 50),
        module("jit_argmax(13)", 70, 75),
        module("jit_serve_decode_w2(14)", 160, 200),
    ]


@pytest.fixture
def trace():
    return pt.reduce(harness_rows() + program_rows())


def test_program_rows_leave_the_benchmarks_readings_unchanged(trace):
    alone = tr.summarize(harness_rows())
    assert tr.busy(trace.summary) == tr.busy(alone)
    assert tr.idle_share(trace.summary) == tr.idle_share(alone)
    assert tr.matmul_seconds(trace.summary) == tr.matmul_seconds(alone)
    assert tr.top_ops(trace.summary) == tr.top_ops(alone)
    assert tr.idle_gaps(trace.summary) == tr.idle_gaps(alone)
    # a Pallas kernel is named by the deployed configuration that ran
    assert ["pallas:mm_bm8_bn512_bk512_mnk", pytest.approx(20e-3)] in tr.top_ops(alone)


def test_the_three_readings(trace):
    assert [s.step_id for s in trace.decode_steps(4)] == [0]
    assert trace.program_of(12 * MS) == "jit_gather" and trace.program_of(60 * MS) is None
    # gather 5 ms + argmax 5 ms, outside the step programs
    assert pt.kvpool_device_ms(trace, 4) == pytest.approx(10)
    assert pt.decode_program_ms(trace, 4) == pytest.approx(28)
    assert pt.decode_program_ms(trace, 2) == pytest.approx(40)
    # idle while dispatching: [4, 10) and [15, 22) of the 250 ms inside step spans
    assert pt.dispatch_idle_pct(trace) == pytest.approx(100 * 13 / 250)
    # device ms by program; the loop container is not counted twice
    assert pt.program_ms(trace, 4) == pytest.approx(
        {"jit_serve_decode_w4": 28, "jit_gather": 5, "jit_argmax": 5})


def test_without_program_names_the_split_is_not_guessed():
    bare = pt.reduce(harness_rows() + [r for r in program_rows() if r[1] != pt.MODULES_LINE])
    assert pt.kvpool_device_ms(bare, 4) is None and pt.decode_program_ms(bare, 4) is None


def test_idle_is_named_by_the_innermost_span(trace):
    assert pt.idle_by_span(trace, 4) == pytest.approx({
        "serve.sample": 20, "serve.decode": 16, "kvpool.gather": 10, "serve.step": 9,
        "engine.step": 4, "serve.launch": 3})
    # the 62 ms of idleness in step 0, all of it named
    assert sum(pt.idle_by_span(trace, 4).values()) == pytest.approx(10 + 7 + 20 + 25)
    assert pt.idle_gaps(trace) == [
        ["serve.decode", pytest.approx(0.1)], ["serve.sample", pytest.approx(0.085)],
        ["serve.decode", pytest.approx(0.02)], ["engine.step", pytest.approx(0.01)],
        ["kvpool.gather", pytest.approx(0.007)]]
    # the harness's own naming of the same gaps sees its spans alone
    assert {g[0] for g in tr.idle_gaps(trace.summary)} == {"engine.step"}


def test_recorded_trace():
    """Three full-width decode steps of phi4-mini-3.8b.chat recorded on a TPU
    v5e with the program's spans on (``spans.py --keep-trace``; HLO text cut
    to name, opcode, operands, kind and custom-call target, device stats
    dropped).  The third step holds one of the cell's long idle gaps."""
    trace = pt.reduce(tr.load_rows(DATA / "trace_spans.json.gz"))
    assert [s.step_id for s in trace.decode_steps(4)] == [505, 506, 507]
    # the pool's eager copies, per step, and one decode program
    assert pt.kvpool_device_ms(trace, 4) == pytest.approx(45.799068)
    assert pt.decode_program_ms(trace, 4) == pytest.approx(28.144801)
    programs = pt.program_ms(trace, 4)
    assert list(programs)[:5] == ["jit_serve_decode_w4", "jit__moveaxis", "jit_scatter",
                                  "jit_reshape", "jit__take"]
    assert pt.dispatch_idle_pct(trace) == pytest.approx(45.06724997892376)
    idle = pt.idle_by_span(trace, 4)
    assert list(idle)[:3] == ["kvpool.scatter", "serve.launch", "serve.sample"]
    assert idle["kvpool.scatter"] == pytest.approx(56.33412)
    # the long gap and the usual ones, named by what the host was in
    assert pt.idle_gaps(trace, 3) == [["kvpool.scatter", pytest.approx(0.122634911)],
                                      ["kvpool.scatter", pytest.approx(0.012787479)],
                                      ["kvpool.scatter", pytest.approx(0.012787188)]]
    # the benchmark's own readings of the same rows
    busy, window = tr.busy(trace.summary)
    assert busy == pytest.approx(0.22182442) and window == pytest.approx(0.442704969)
    assert tr.matmul_seconds(trace.summary) == pytest.approx(0.053487972)
    assert "pallas:mm_bm8_bn512_bk1024_nmk" in [n for n, _ in tr.top_ops(trace.summary)]


def test_trace_0_leaves_the_programs_spans_off(tiny_root, monkeypatch):
    from repro import trace
    from repro.serve.engine import ServingEngine

    seen = []
    orig = ServingEngine.step

    def step(self):
        seen.append(trace._record is not None)
        return orig(self)

    monkeypatch.setattr(ServingEngine, "step", step)
    out = tiny_run(tiny_root, 3, seconds=1.0)
    assert out["metrics"] and seen and not any(seen)


def test_a_window_with_the_spans_on(tiny_root):
    import json

    from chipbench import harness, spans

    # a burst that fills the four lanes, so the window decodes at full width
    mix = tiny_root / "chipbench" / "traffic" / "tiny.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()), lead_in_burst=12)))
    sess = harness.prepare(TINY, 2**31 + 5, t_start=harness.clock(), root=tiny_root,
                           require_chip=False, tune_device="tpu_v5e")
    out = spans.measure(sess, 2**31 + 5, 2.0, t_start=harness.clock())
    # the program's spans reached the profiler's host plane, attributes and all
    assert {"serve.step", "serve.decode", "serve.launch", "kvpool.gather",
            "kvpool.scatter", "serve.sample"} <= set(out["host_spans"])
    assert out["decode_steps_traced"] >= 1
    assert set(out["metrics"]) == {m["name"] for m in sess.cell.per_layer}
    assert out["self_ms"]["serve.decode"] > 0
    # the CPU has no device plane: nothing to split, and nothing guessed
    assert out["kvpool_device_ms"] is None and out["decode_program_ms"] is None
