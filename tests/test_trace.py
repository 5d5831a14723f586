"""Spans inside the serving step (``repro.trace``), on the toy engines of
``test_serve_engine.py``: what they record when on, and that off they cost
no clock read and change no token."""
import time
import types

import jax
import pytest

from repro import trace
from repro.serve.engine import ServingEngine
from test_serve_engine import ChunkToyModel, ToyModel

PROMPTS = [list(range(1, 11)), list(range(3, 24)), [5, 6, 7]]


def _serve(model, traced: bool, **kw):
    """Serve PROMPTS to completion; (outputs, record or None, decode widths)."""
    eng = ServingEngine(model, params={}, **kw)
    widths = []
    eng.on_decode = widths.append
    tickets = [eng.submit(p, max_new_tokens=5) for p in PROMPTS]
    if traced:
        with trace.record() as rec:
            eng.drain()
    else:
        rec = None
        eng.drain()
    return [t.request for t in tickets], rec, widths


CHUNKED = dict(max_batch=2, cache_len=64, block_size=8, prefill_buckets=(8, 16),
               prefill_chunk_tokens=8)
LEGACY = dict(max_batch=2, cache_len=64, prefill_buckets=(8, 16))


@pytest.fixture(scope="module")
def served():
    on = _serve(ChunkToyModel(), True, **CHUNKED)
    off = _serve(ChunkToyModel(), False, **CHUNKED)
    legacy = _serve(ToyModel(), True, **LEGACY)
    return on, off, legacy


def named(rec, name):
    return [s for s in rec.spans if s.name == name]


def _root(rec, s):
    while s.parent is not None:
        s = rec.spans[s.parent]
    return s


def _every_span_nests_under_one_step(on, off, legacy):
    for _, rec, _ in (on, legacy):
        assert all(s is not None for s in rec.spans)  # every span closed
        roots = [s for s in rec.spans if s.parent is None]
        assert roots and all(s.name == "serve.step" for s in roots)
        assert [s.attrs["step"] for s in roots] == sorted(s.attrs["step"] for s in roots)
        for s in rec.spans:
            p = rec.spans[s.parent] if s.parent is not None else None
            assert p is None or p.start <= s.start <= s.end <= p.end
            assert _root(rec, s).name == "serve.step"


def _chunks_carry_the_uid_and_cover_the_prompt(on, off, legacy):
    reqs, rec, _ = on
    chunks = named(rec, "serve.chunk")
    for r in reqs:
        mine = [c.attrs for c in chunks if c.attrs["request"] == r.uid]
        assert sum(c["n_real"] for c in mine) == len(r.prompt)
        assert [c["start"] for c in mine] == sorted(c["start"] for c in mine)
        assert [c["final"] for c in mine] == [False] * (len(mine) - 1) + [True]
    assert {s.attrs["request"] for s in named(rec, "serve.activate")} == {r.uid for r in reqs}


def _one_decode_span_per_decode_round(on, off, legacy):
    for _, rec, widths in (on, legacy):
        decodes = named(rec, "serve.decode")
        assert [d.attrs["width"] for d in decodes] == widths
        assert all(1 <= d.attrs["rows"] <= d.attrs["width"] for d in decodes)
        assert len(named(rec, "serve.sample")) == len(widths)


def _compile_only_on_the_first_call_of_each_width(on, off, legacy):
    for _, rec, _ in (on, legacy):
        calls = [s for s in rec.spans if s.name in ("serve.compile", "serve.launch")]
        seen = set()
        for s in calls:
            key = (s.attrs["site"], s.attrs["width"])
            assert (s.name == "serve.compile") == (key not in seen), key
            seen.add(key)
        assert {k for k in seen if k[0] == "engine.decode"} == {("engine.decode", 1),
                                                                 ("engine.decode", 2)}


def _pool_copies_run_inside_engine_phases(on, off, legacy):
    reqs, rec, _ = on
    parents = {rec.spans[s.parent].name for s in named(rec, "kvpool.gather")}
    assert parents == {"serve.launch", "serve.compile"}  # the gather feeds the program
    assert {rec.spans[s.parent].name for s in named(rec, "kvpool.scatter")} == {
        "serve.chunk", "serve.decode"}
    assert named(rec, "kvpool.index")  # block hashing on admission and after chunks
    _, lrec, _ = legacy
    assert [s.attrs["request"] for s in named(lrec, "serve.prefill")] == [
        r.uid for r in legacy[0]]
    assert {lrec.spans[s.parent].name for s in named(lrec, "kvpool.admit")} == {"serve.prefill"}
    sched = named(rec, "serve.schedule")
    assert sum(s.attrs.get("admitted", 0) for s in sched) == len(reqs)


def _tokens_are_the_same_with_tracing_on_and_off(on, off, legacy):
    assert [r.output for r in on[0]] == [r.output for r in off[0]]
    assert all(r.done and len(r.output) == 5 for r in on[0] + legacy[0])


CASES = {f.__name__.lstrip("_"): f for f in (
    _every_span_nests_under_one_step,
    _chunks_carry_the_uid_and_cover_the_prompt,
    _one_decode_span_per_decode_round,
    _compile_only_on_the_first_call_of_each_width,
    _pool_copies_run_inside_engine_phases,
    _tokens_are_the_same_with_tracing_on_and_off,
)}


@pytest.mark.parametrize("case", list(CASES))
def test_spans_with_tracing_on(served, case):
    CASES[case](*served)


def test_off_span_is_the_shared_null_and_reads_no_clock(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a span read the clock or opened an annotation while off")

    monkeypatch.setattr(trace, "time", types.SimpleNamespace(perf_counter=refuse))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert trace._record is None
    s = trace.span("serve.step", step=0)
    assert s is trace._NULL and trace.span("kvpool.gather", lanes=4) is s
    with s as inner:
        inner.set(admitted=1)
    eng = ServingEngine(ChunkToyModel(), params={}, **CHUNKED)
    eng.submit(PROMPTS[0], max_new_tokens=3)
    assert eng.drain().completed == 1
    assert trace._record is None


def test_record_hands_over_closed_spans_and_refuses_nesting():
    with trace.record() as rec:
        with pytest.raises(RuntimeError):
            with trace.record():
                pass
        with trace.span("serve.step", step=3) as outer:
            with pytest.raises(ValueError):
                with trace.span("serve.decode", width=4, rows=2):
                    raise ValueError
            outer.set(done=1)
            t = time.perf_counter()
    assert trace._record is None and trace.span("serve.step") is trace._NULL
    step, decode = rec.spans
    assert step.parent is None and decode.parent == 0
    assert step.attrs == {"step": 3, "done": 1} and decode.attrs == {"width": 4, "rows": 2}
    assert step.start <= decode.start <= decode.end <= t <= step.end
