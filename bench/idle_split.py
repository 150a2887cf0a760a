"""The device's idle time in a profiler trace, split by the program's spans.

Reads the ``.xplane.pb`` that ``bench/trace_reduce.py`` reduces, over the
same window: the run's traced span on the host clock
(``RunContext.trace_span``, whose length is the reduction's ``window_s``),
placed on the trace's clock by ``trace_window``. Device busy time is the
union of the TPU planes' ``XLA Ops`` intervals, as in ``trace_reduce``,
clipped to that window, so the split's idle time is the reduction's
``window_s - busy_s`` less any device work outside the window.

The host planes' ``vizier.*`` events are the ``jax.profiler`` annotations
that ``repro.tracing`` opens with each span, their counts as the event's
stats (any ``#...#`` metadata suffix is stripped from the name). Each line
of a host plane is one thread.
At each instant a thread is in the innermost program span it has open,
and that span decides what the thread does:

* it waits when the span's name ends in ``.wait`` (a lease wait, a
  long-poll park, a lock wait; so a ``WaitOperation`` dispatch parked in
  ``vizier.op.wait`` waits), or when it is a client's ``vizier.rpc.call``:
  the client is blocked on its server, whose own spans say what is being
  worked on;
* it works in any other span.

Each idle instant of the window is counted once, in one of three parts:

* ``working``: some thread works. The instant goes to the working span
  that started last among the threads' innermost ones. This is the idle
  time a host-side change can recover;
* ``waiting``: threads are in program spans, and all of them wait;
* ``no_span``: no program span is open.

A span that opened before the trace started is not in the trace: until
it ends, its time counts as no span.
"""

from __future__ import annotations

import bisect
import dataclasses
import statistics
from typing import Dict, List, Optional, Tuple

from bench.trace_reduce import _union

PREFIX = "vizier."
DISPATCH = "vizier.rpc.dispatch"
BLOCKED = ("vizier.rpc.call",)   # working-named spans in which a thread waits


@dataclasses.dataclass
class IdleSplit:
    window_s: float
    idle_s: float
    working_s: float
    waiting_s: float
    no_span_s: float
    by_span: List[Tuple[str, float]]   # working idle s per span, most first

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def span_name(event_name: str) -> str:
    """``vizier.rpc.dispatch#method=GetTrial#`` -> ``vizier.rpc.dispatch``."""
    return event_name.split("#", 1)[0]


def span_meta(ev) -> Dict[str, str]:
    """An annotation's counts, from the event's stats."""
    return {str(k): str(v) for k, v in ev.stats}


def waits(name: str) -> bool:
    return name.endswith(".wait") or name in BLOCKED


def _host_events(pdata):
    """(event, thread) of the program spans in the trace; the thread is the
    event's line, numbered across the host planes."""
    thread = 0
    for plane in pdata.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    yield ev, thread
            thread += 1


def host_spans(pdata) -> List[Tuple[float, float, str, int]]:
    """(start, end, span name, thread) of the program spans in the trace."""
    return [(ev.start_ns, ev.end_ns, span_name(ev.name), thread)
            for ev, thread in _host_events(pdata)]


def trace_window(pdata, records, trace_span) -> Optional[Tuple[float, float]]:
    """``trace_span`` (perf_counter seconds) on the trace's clock.

    The offset between the clocks is the median, over the RPC dispatch
    spans found both in ``records`` (``repro.tracing.snapshot()``) and in
    the trace, of the annotation's start less the span's recorded start,
    matched by request id. None when no span matches."""
    started = {str(r.counts["rid"]): r.start_ns for r in records
               if r.name == DISPATCH and "rid" in r.counts}
    offsets = []
    for ev, _thread in _host_events(pdata):
        if span_name(ev.name) == DISPATCH:
            rid = span_meta(ev).get("rid")
            if rid in started:
                offsets.append(ev.start_ns - started[rid])
    if not offsets:
        return None
    off = statistics.median(offsets)
    return trace_span[0] * 1e9 + off, trace_span[1] * 1e9 + off


def _device_busy(pdata) -> List[Tuple[float, float]]:
    busy = []
    for plane in pdata.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    busy.extend((ev.start_ns, ev.end_ns) for ev in line.events)
    return _union(busy)


def _idle(pdata, lo: float, hi: float) -> List[Tuple[float, float]]:
    idle: List[Tuple[float, float]] = []
    t = lo
    for s, e in _device_busy(pdata):
        if s > t:
            idle.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        idle.append((t, hi))
    return [(s, e) for s, e in idle if e > s]


def split(pdata, lo_ns: float, hi_ns: float) -> IdleSplit:
    """The device idle of ``[lo_ns, hi_ns]`` (trace clock), split three ways
    (module doc)."""
    idle = _idle(pdata, lo_ns, hi_ns)
    idle_starts = [s for s, _ in idle]
    spans = [(max(s, lo_ns), min(e, hi_ns), n, t)
             for s, e, n, t in host_spans(pdata)
             if e > s and e > lo_ns and s < hi_ns]
    starts: Dict[float, list] = {}
    ends: Dict[float, list] = {}
    for i, (s, e, _n, _t) in enumerate(spans):
        starts.setdefault(s, []).append(i)
        ends.setdefault(e, []).append(i)
    times = sorted(set(starts) | set(ends) | {lo_ns, hi_ns}
                   | {x for iv in idle for x in iv})

    open_on: Dict[int, set] = {}     # thread -> its open spans
    inner: Dict[int, int] = {}       # thread -> its innermost open span

    def innermost(thread: int) -> None:
        got = open_on.get(thread)
        if got:   # the latest start; of two that start together, the shorter
            inner[thread] = max(got, key=lambda i: (spans[i][0], -spans[i][1]))
        else:
            inner.pop(thread, None)

    by_span: Dict[str, float] = {}
    parts = {"working": 0.0, "waiting": 0.0, "no_span": 0.0}
    for a, b in zip(times, times[1:]):
        changed = set()
        for i in ends.get(a, ()):
            open_on[spans[i][3]].discard(i)
            changed.add(spans[i][3])
        for i in starts.get(a, ()):
            open_on.setdefault(spans[i][3], set()).add(i)
            changed.add(spans[i][3])
        for thread in changed:
            innermost(thread)
        k = bisect.bisect_right(idle_starts, a) - 1
        if k < 0 or idle[k][1] < b:
            continue             # the device is busy in [a, b]
        dt = (b - a) * 1e-9
        working = [i for i in inner.values() if not waits(spans[i][2])]
        if working:
            parts["working"] += dt
            name = spans[max(working, key=lambda i: spans[i][0])][2]
            by_span[name] = by_span.get(name, 0.0) + dt
        elif inner:
            parts["waiting"] += dt
        else:
            parts["no_span"] += dt
    return IdleSplit(
        window_s=(hi_ns - lo_ns) * 1e-9,
        idle_s=sum(e - s for s, e in idle) * 1e-9,
        working_s=parts["working"], waiting_s=parts["waiting"],
        no_span_s=parts["no_span"],
        by_span=sorted(by_span.items(), key=lambda kv: -kv[1]))
