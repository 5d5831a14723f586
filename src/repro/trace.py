"""Spans at the serving path's layer boundaries.

The program's only tracing module.  A span marks one layer's work on the
host: the engine's phases (``serve.*``) and the KV pool's dense-view copies
and block hashing (``kvpool.*``)::

    with repro.trace.record() as rec:      # spans are off outside this
        engine.step()
    rec.spans                              # name, start, end, parent, attrs

Off (the default), :func:`span` makes one check of a module flag and returns
a shared null context: it reads no clock and records nothing.  On, each span
is kept in memory with its start and end on :func:`time.perf_counter` (the
clock a benchmark harness times requests on), the index of its enclosing
span and its attributes, and is also opened as a
:class:`jax.profiler.TraceAnnotation`, so that inside a profiler session it
lands on the host plane of the device trace, on the device trace's clock.
Spans only record: none waits for the device.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax

__all__ = ["Record", "Span", "record", "span"]


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float  # time.perf_counter() seconds
    end: float
    parent: int | None  # index in Record.spans of the enclosing span
    attrs: dict


class Record:
    """The spans of one :func:`record` block, in the order they opened."""

    def __init__(self):
        self.spans: list[Span | None] = []  # a slot is filled when its span closes
        self._open: list[int] = []


class _NullSpan:
    """What :func:`span` returns while nothing records."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set(self, **attrs) -> None:
        pass


_NULL = _NullSpan()
_record: Record | None = None


class _Span:
    __slots__ = ("rec", "name", "attrs", "index", "start", "_annotation")

    def __init__(self, rec: Record, name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        rec = self.rec
        self._annotation = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._annotation.__enter__()
        self.index = len(rec.spans)
        rec.spans.append(None)
        rec._open.append(self.index)
        self.start = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        """Attributes known only once the span's work is done."""
        self.attrs.update(attrs)
        self._annotation.set_metadata(**attrs)

    def __exit__(self, *exc):
        end = time.perf_counter()
        rec = self.rec
        rec._open.pop()
        parent = rec._open[-1] if rec._open else None
        rec.spans[self.index] = Span(self.name, self.start, end, parent, self.attrs)
        self._annotation.__exit__(*exc)
        return None


def span(name: str, **attrs):
    """A context manager over one layer's work; ``attrs`` are ints or strings."""
    rec = _record
    if rec is None:
        return _NULL
    return _Span(rec, name, attrs)


@contextlib.contextmanager
def record():
    """Turn spans on for the block; yields the :class:`Record` they fill."""
    global _record
    if _record is not None:
        raise RuntimeError("spans are already being recorded")
    rec = _record = Record()
    try:
        yield rec
    finally:
        _record = None
