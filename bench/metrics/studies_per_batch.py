"""Work queue: distinct studies per worker batch (the ``studies`` count of
the ``vizier.worker.batch`` spans that ended in the window), mean: how many
studies one lease hands a worker, the width a cross-study batch could fuse.
A program whose batch span has no such count reads nothing."""

import numpy as np

from bench.lib import spans

UNIT, BETTER, SOURCE = "studies", "higher", "program_counter"
LAYER = "work queue"
MOVES = "suggestions_per_s"


def read(ctx):
    w = spans.window(ctx)
    if w is None:
        return None
    studies = [r.counts["studies"] for r in w.spans
               if r.name == spans.BATCH and r.counts.get("studies")
               and w.t0_ns <= r.end_ns <= w.t1_ns]
    return float(np.mean(studies)) if studies else None
