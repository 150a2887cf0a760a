"""The program's own spans (``repro.tracing``) over a run's measured window.

The readers of the span metrics call ``window(ctx)`` after the window, in
the run's own process. It returns None when the program under test records
no spans (a checkout from before ``repro.tracing``): the reader then
returns None and the metric is left out of the result line.

"Served" ops are those of the worker batches (``vizier.worker.batch``)
that started inside ``[ctx.t0, ctx.t0 + ctx.seconds]``; a per-op metric
divides by their number. A span's self time is its wall time less that of
its direct children (a query less its connection-lock wait).
"""

from __future__ import annotations

from typing import Dict, List, Optional

BATCH = "vizier.worker.batch"


class Window:
    def __init__(self, spans: list, t0_ns: int, t1_ns: int):
        self.spans = spans
        self.t0_ns, self.t1_ns = t0_ns, t1_ns
        self.by_id = {r.span_id: r for r in spans}
        self.children: Dict[int, list] = {}
        for r in spans:
            if r.parent_id is not None:
                self.children.setdefault(r.parent_id, []).append(r)
        self.batches = [r for r in spans
                        if r.name == BATCH and t0_ns <= r.start_ns <= t1_ns]
        self._batch_ids = {r.span_id for r in self.batches}

    def started(self, name: str) -> list:
        """Spans named ``name`` that started inside the window."""
        return [r for r in self.spans if r.name == name
                and self.t0_ns <= r.start_ns <= self.t1_ns]

    def in_batches(self, name: str) -> list:
        """Spans named ``name`` inside a served batch."""
        out = []
        for r in self.spans:
            if r.name != name:
                continue
            p = r
            while p.parent_id in self.by_id:
                p = self.by_id[p.parent_id]
                if p.span_id in self._batch_ids:
                    out.append(r)
                    break
        return out

    def served_ops(self) -> int:
        return sum(int(r.counts.get("ops", 0)) for r in self.batches)

    def served_op_names(self) -> List[str]:
        return [name for r in self.batches for name in r.trace_id]

    def self_ns(self, r) -> int:
        kids = self.children.get(r.span_id, ())
        return r.wall_ns - sum(c.wall_ns for c in kids)

    def per_served_op_ms(self, total_ns: float) -> Optional[float]:
        n = self.served_ops()
        return total_ns * 1e-6 / n if n else None


def window(ctx) -> Optional[Window]:
    try:
        from repro import tracing
    except ImportError:
        return None
    t0 = int(ctx.t0 * 1e9)
    t1 = t0 + int(ctx.seconds * 1e9)
    w = Window(tracing.snapshot(t0, t1), t0, t1)
    return w if w.batches else None
