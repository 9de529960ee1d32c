"""Spans and counters of the program, kept in memory.

    from repro import tracing
    with tracing.span("sim.grid"):          # a root: assigns the call id
        with tracing.span("sim.lower"):     # a child: same call id
            ...
        tracing.count("sim.traces")
    tracing.snapshot()                      # counters and the ring

Every span records its name, its start and end on ``time.perf_counter``,
its parent and the id of the call it belongs to: the outermost span
open in the current context is the root of a call, and its own id is the
call id of every span opened inside it. Each span is also a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so an active
profiler trace holds it on the host plane, on the clock of the device's
operations; with no profiler active that costs well under a microsecond.

The recorder keeps the most recent ``RING`` finished spans, whole, and
the counters. Nothing is written out: whoever wants them reads
``snapshot()``. The process has one recorder, ``RECORDER``, behind the
module's functions; a test may build its own.
"""
from __future__ import annotations

import contextvars
import itertools
import threading
from collections import deque
from time import perf_counter
from typing import NamedTuple

from jax.profiler import TraceAnnotation

__all__ = ["Recorder", "RECORDER", "SpanRecord", "span", "count",
           "counter", "snapshot", "reset", "PREFIX", "RING"]

#: prefix of every span's name in a profiler trace
PREFIX = "repro."
#: finished spans the ring keeps
RING = 4096


class SpanRecord(NamedTuple):
    """One finished span. ``parent`` is the id of the span it was opened
    in (None for a root); ``call`` is its root's id."""
    name: str
    start_s: float
    end_s: float
    id: int
    parent: int | None
    call: int


class _Span:
    __slots__ = ("rec", "name", "id", "parent", "call", "t0", "ann",
                 "token")

    def __init__(self, rec: Recorder, name: str):
        self.rec, self.name = rec, name

    def __enter__(self):
        rec = self.rec
        self.parent = rec._open.get()
        self.id = next(rec._ids)
        self.call = self.id if self.parent is None else self.parent.call
        self.token = rec._open.set(self)
        self.ann = TraceAnnotation(PREFIX + self.name)
        self.ann.__enter__()
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = perf_counter()
        self.ann.__exit__(*exc)
        rec = self.rec
        rec._open.reset(self.token)
        parent = self.parent
        with rec._lock:
            rec._ring.append(SpanRecord(
                self.name, self.t0, t1, self.id,
                None if parent is None else parent.id, self.call))
        return False


class Recorder:
    """Counters and a ring of the last ``RING`` spans."""

    def __init__(self):
        self._open = contextvars.ContextVar("repro_tracing_open",
                                            default=None)
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._counters: dict = {}
        self._ring = deque(maxlen=RING)

    def span(self, name: str) -> _Span:
        """A context manager that records one span named ``name``."""
        return _Span(self, name)

    def count(self, name: str, n=1) -> None:
        """Add ``n`` to the counter ``name``."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def counter(self, name: str):
        """The counter's value, 0 if it was never counted."""
        return self._counters.get(name, 0)

    def snapshot(self) -> dict:
        """``counters``: name -> value; ``ring``: the kept
        ``SpanRecord`` s, oldest end first."""
        with self._lock:
            return {"counters": dict(self._counters),
                    "ring": list(self._ring)}

    def reset(self) -> None:
        """Clear every counter and the ring."""
        with self._lock:
            self._counters.clear()
            self._ring.clear()


#: the process's recorder
RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
counter = RECORDER.counter
snapshot = RECORDER.snapshot
reset = RECORDER.reset
