"""What a metric reader gets: everything one run measured, and helpers.

Each file ``bench/metrics/<name>.py`` defines ``read(ctx) -> float | None``
and states ``UNIT``, ``BETTER``, ``SOURCE``, ``LAYER`` and ``MOVES`` (the
end-to-end metric it moves; ``None`` for an end-to-end metric). A reader
returns ``None`` when the run gave it nothing to read, and the harness then
leaves the metric out of the result line.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import numpy as np


@dataclasses.dataclass
class RunContext:
    cell: Any                 # cells.Cell
    plan: Any                 # plan.Plan
    seconds: float
    t_proc: float             # perf_counter at the top of run.py
    t0: float                 # the window opens (first op due)
    close: float              # the last op was due
    grace_s: float
    records: list             # loadgen.OpRecord, window ops only
    unfinished_due: List[float]   # due times of events that never ended
    late_s: List[float]
    recorder: Any             # capture.Recorder
    trace: Optional[Any] = None   # trace_reduce.Reduction (traced runs)
    peaks: Optional[dict] = None
    window_compiles: int = 0
    trace_span: Optional[tuple] = None   # perf_counter bounds of the trace

    # -- client side -------------------------------------------------------
    def latencies_ms(self, kind: str) -> np.ndarray:
        """Due-to-done latency of every op of ``kind`` due in the window;
        an op that failed or never returned counts as one that returned
        at the end of the grace period."""
        limit = self.close + self.grace_s
        out = [((r.done if r.ok else limit) - r.due) * 1e3
               for r in self.records if r.kind == kind]
        if kind == "suggest":
            out += [(limit - due) * 1e3 for due in self.unfinished_due]
        return np.asarray(out, np.float64)

    def suggest_ops(self) -> list:
        return [r for r in self.records if r.kind == "suggest" and r.ok]

    # -- server side ---------------------------------------------------------
    def served_ops(self) -> int:
        """Suggest ops that a worker batch ran while the recorder was on."""
        return sum(len(b.ops) for b in self.recorder.batches)

    def queue_waits_s(self) -> List[float]:
        enq = self.recorder.enqueued
        return [b.t0 - enq[op] for b in self.recorder.batches
                for op in b.ops if op in enq]

    def policy_s_per_op(self) -> List[float]:
        return [b.policy_s.get(b.op_study[op], 0.0)
                for b in self.recorder.batches for op in b.ops]

    def per_served_op(self, total: float) -> Optional[float]:
        n = self.served_ops()
        return total / n if n else None

    def per_traced_op(self, total: float) -> Optional[float]:
        """``total`` (device time from the trace) per suggest op whose
        worker batch started inside the traced span."""
        a, b = self.trace_span
        n = sum(len(bt.ops) for bt in self.recorder.batches if a <= bt.t0 <= b)
        return total / n if n else None


def percentile(values: np.ndarray, q: float) -> Optional[float]:
    if len(values) == 0:
        return None
    return float(np.percentile(values, q))
