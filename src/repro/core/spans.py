"""Spans: the host phases of a coloring call, kept in memory and on the
profiler's clock.

``span(name)`` is a context manager around one phase of host work. It
enters ``jax.profiler.TraceAnnotation(name)``, so a profiler trace shows
the phase on the same timeline as the device's ops, and, inside a call, it
records the phase's name, start and end (``time.perf_counter_ns``), parent
span and call id in that call's record. Outside any call it is the
annotation alone and records nothing.

``call(name)`` opens a call: the root span of one record. Once the root
closes, the record joins a bounded ring of finished calls, the newest
``CAPACITY`` of them. A ``call`` inside an open call is a span of
the outer call. ``ColoringPlan.__call__`` opens one call per coloring,
``plan.call``, whose children are:

    plan.canonicalize  constraint graph, ordering relabel, envelope checks
    plan.layout        host arrays of the device layout: directed edges,
                       ELL slot map, padding
    plan.transfer      every host-to-device copy of the call, one
                       ``jax.device_put``, blocked on until the arrays are
                       on the device
    plan.device        the compiled program, from dispatch until its
                       outputs are ready
    plan.report        fetch, un-relabel, the report

Each thread keeps its own stack of open spans, so concurrent calls from
different threads never share a record. A span left by an exception is
closed and marked ``error``.

``count(name, value)`` adds to a counter of the open call (nothing outside
a call): ``plan.call`` records ``wavefront_spills``, the report's count of
offset classes that overflowed the lockstep wavefront's window.

An operator reads the per-call breakdown, newest last, with
:func:`recent_calls`::

    from repro.core import spans
    for c in spans.recent_calls(5):
        print(c.call_id, c.error,
              {s.name: s.duration_ns / 1e6 for s in c.spans})   # ms

and a phase's self time (its duration less what its children cover) with
``CallRecord.self_ns``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import threading
import time
from typing import Callable, Dict, Iterator, List

import jax

CAPACITY = 4096


@dataclasses.dataclass
class SpanRecord:
    """One phase of a call. ``parent`` is the index of the enclosing span
    in the call's ``spans`` (-1 for the root); ``end_ns`` is -1 while the
    span is open."""

    name: str
    call_id: int
    parent: int
    start_ns: int
    end_ns: int = -1
    error: bool = False

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class CallRecord:
    """The spans of one call, in the order they started; ``spans[0]`` is
    the root. ``counters`` holds what :func:`count` added during it."""

    call_id: int
    spans: List[SpanRecord] = dataclasses.field(default_factory=list)
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def root(self) -> SpanRecord:
        return self.spans[0]

    @property
    def error(self) -> bool:
        return self.root.error

    def total_ns(self, name: str) -> int:
        """Summed duration of the call's spans named ``name`` (0 if none)."""
        return sum(s.duration_ns for s in self.spans if s.name == name)

    def self_ns(self, index: int) -> int:
        """Duration of ``spans[index]`` less the time its children cover
        (children of one span never overlap: a thread runs one at a time)."""
        return self.spans[index].duration_ns - sum(
            s.duration_ns for s in self.spans if s.parent == index)


class Recorder:
    """Per-thread stacks of open spans and the ring of finished calls. The
    module's functions use one recorder for the whole process; ``clock``
    (nanoseconds) is there for tests."""

    def __init__(self, capacity: int = CAPACITY,
                 clock: Callable[[], int] = time.perf_counter_ns):
        self._clock = clock
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> list:
        """This thread's open spans, innermost last, as (call, index)."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def _recorded(self, name: str, call: CallRecord) -> Iterator[SpanRecord]:
        stack = self._stack()
        parent = stack[-1][1] if stack else -1
        rec = SpanRecord(name, call.call_id, parent, self._clock())
        call.spans.append(rec)
        stack.append((call, len(call.spans) - 1))
        try:
            with jax.profiler.TraceAnnotation(name):
                yield rec
        except BaseException:
            rec.error = True
            raise
        finally:
            rec.end_ns = self._clock()
            stack.pop()

    def span(self, name: str):
        """A phase of the open call, or only a profiler annotation when this
        thread has no call open."""
        stack = self._stack()
        if not stack:
            return jax.profiler.TraceAnnotation(name)
        return self._recorded(name, stack[-1][0])

    def count(self, name: str, value: int) -> None:
        """Add ``value`` to the open call's counter ``name``; outside a call
        nothing is recorded."""
        stack = self._stack()
        if stack:
            counters = stack[-1][0].counters
            counters[name] = counters.get(name, 0) + int(value)

    @contextlib.contextmanager
    def call(self, name: str) -> Iterator[SpanRecord]:
        """The root span of a new call record; yields the root's
        :class:`SpanRecord`, whose ``end_ns`` is set once it closes."""
        stack = self._stack()
        if stack:
            with self._recorded(name, stack[-1][0]) as rec:
                yield rec
            return
        record = CallRecord(next(self._ids))
        try:
            with self._recorded(name, record) as root:
                yield root
        finally:
            with self._lock:
                self._ring.append(record)

    def recent_calls(self, n: int) -> List[CallRecord]:
        """The last ``n`` finished calls, oldest first (fewer if the ring
        holds fewer)."""
        if n <= 0:
            return []
        with self._lock:
            return list(self._ring)[-n:]


RECORDER = Recorder()
span = RECORDER.span
call = RECORDER.call
count = RECORDER.count
recent_calls = RECORDER.recent_calls
