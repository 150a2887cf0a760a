"""Program spans: where a served suggest or complete op spends its time.

``span(name, trace_id=..., **counts)`` times one layer boundary on the host.
It records, in memory, the span's name, trace id, parent span and thread,
its start and end on ``time.perf_counter_ns()``, the thread's CPU time
inside it (``time.thread_time_ns()``) and the counts the boundary knows
(ops, trials, fit steps; for RPC spans the method and request id). The
same call opens a ``jax.profiler.TraceAnnotation`` of the same name, so
while a profiler trace is active every span sits on the device trace's
clock beside the programs it launched.

A span whose name ends in ``.wait`` marks time the thread deliberately
blocks (a lease wait, a long-poll park, a lock wait); every other span is
working time. Wall time less thread CPU time is what a working span spent
off the CPU: waiting for the interpreter lock, a lock or I/O.

Trace ids. Spans of one suggest op carry the op's name: the handler that
creates the op names the spans it has open with ``bind``; a span opened
without an id takes its parent's; a worker batch carries the tuple of the
names of the ops it serves. ``record`` keeps an interval that starts on
one thread and ends on another (an op's time in the queue) in memory
only, with no annotation. The client's ``vizier.rpc.call`` and the
server's ``vizier.rpc.dispatch`` of one frame share its request id.

Storage is a ring of the last ``CAPACITY`` spans, always on, with no file
or exporter: a worker that reports eight trials and asks for more opens
about seventy spans on the server and its client, and an idle Pythia
worker one per lease poll, so the ring holds the last several minutes of
a busy server. ``snapshot(t0_ns, t1_ns)`` returns
the spans that overlap a window.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

CAPACITY = 1 << 16


class SpanRecord(NamedTuple):
    name: str
    trace_id: Any              # an op name, a tuple of op names, or None
    span_id: int
    parent_id: Optional[int]
    thread_id: int
    start_ns: int
    end_ns: int
    cpu_ns: Optional[int]      # thread CPU time; None across threads
    counts: Dict[str, Any]

    @property
    def wall_ns(self) -> int:
        return self.end_ns - self.start_ns

    @property
    def waiting(self) -> bool:
        return self.name.endswith(".wait")

    def serves(self, op_name: str) -> bool:
        """True when the span belongs to ``op_name``'s trace."""
        tid = self.trace_id
        return tid == op_name or (isinstance(tid, tuple) and op_name in tid)


class SpanRing:
    """The last ``capacity`` spans. Writers take no lock: the slot index
    comes from an atomic counter and a slot store is one bytecode."""

    def __init__(self, capacity: int):
        self._slots: List[Optional[tuple]] = [None] * capacity
        self._seq = itertools.count()

    def add(self, record: tuple) -> None:
        slots = self._slots
        slots[next(self._seq) % len(slots)] = record

    def snapshot(self, t0_ns: Optional[int] = None,
                 t1_ns: Optional[int] = None) -> List[SpanRecord]:
        """The spans overlapping ``[t0_ns, t1_ns]``, by start time. A span
        recorded without a trace id takes its nearest ancestor's."""
        records = [SpanRecord._make(r) for r in list(self._slots)
                   if r is not None]
        by_id = {r.span_id: r for r in records}

        def trace_of(r: SpanRecord):
            while r.trace_id is None and r.parent_id in by_id:
                r = by_id[r.parent_id]
            return r.trace_id

        lo = t0_ns if t0_ns is not None else -1
        hi = t1_ns if t1_ns is not None else float("inf")
        out = [r if r.trace_id is not None
               else r._replace(trace_id=trace_of(r))
               for r in records if r.end_ns >= lo and r.start_ns <= hi]
        out.sort(key=lambda r: r.start_ns)
        return out


_RING = SpanRing(CAPACITY)
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class Span:
    """One open span; ``span()`` makes it. ``end()`` closes it early (a
    lock wait ends once the lock is held); leaving the ``with`` block
    closes it otherwise."""

    __slots__ = ("name", "trace_id", "counts", "_id", "_parent", "_t0",
                 "_c0", "_note")

    def __init__(self, name: str, trace_id: Any, counts: Dict[str, Any]):
        self.name = name
        self.trace_id = trace_id
        self.counts = counts
        self._note = None

    def __enter__(self) -> "Span":
        stack = _stack()
        parent = stack[-1] if stack else None
        if parent is not None:
            self._parent = parent._id
            if self.trace_id is None:
                self.trace_id = parent.trace_id
        else:
            self._parent = None
        self._id = next(_ids)
        self._note = TraceAnnotation(self.name, **self.counts)
        self._note.__enter__()
        stack.append(self)
        self._c0 = time.thread_time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def add(self, **counts: Any) -> None:
        """Counts known only once the work is done (ops, fit steps)."""
        self.counts.update(counts)

    def end(self) -> None:
        if self._note is None:
            return
        t1 = time.perf_counter_ns()
        cpu = time.thread_time_ns() - self._c0
        note, self._note = self._note, None
        note.__exit__(None, None, None)
        _stack().remove(self)
        _RING.add((self.name, self.trace_id, self._id, self._parent,
                   threading.get_ident(), self._t0, t1, cpu, self.counts))

    def __exit__(self, *exc) -> None:
        self.end()


def span(name: str, *, trace_id: Any = None, **counts: Any) -> Span:
    """A span named ``name`` (a ``with`` context); see the module doc."""
    return Span(name, trace_id, counts)


def record(name: str, start_ns: int, *, trace_id: Any = None,
           **counts: Any) -> None:
    """Records ``[start_ns, now]`` in memory only: an interval that another
    thread started, so no annotation can span it."""
    _RING.add((name, trace_id, next(_ids), None, threading.get_ident(),
               start_ns, time.perf_counter_ns(), None, counts))


def bind(trace_id: Any) -> None:
    """Gives ``trace_id`` to every span open on this thread that has none:
    the RPC handler that creates an op names its dispatch span."""
    for s in _stack():
        if s.trace_id is None:
            s.trace_id = trace_id


def snapshot(t0_ns: Optional[int] = None,
             t1_ns: Optional[int] = None) -> List[SpanRecord]:
    """The recorded spans that overlap ``[t0_ns, t1_ns]`` (perf_counter ns)."""
    return _RING.snapshot(t0_ns, t1_ns)
