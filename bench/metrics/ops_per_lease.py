"""Work queue: suggest ops per lease a worker was granted (the ``ops``
count of the ``vizier.lease.wait`` spans that ended with a grant in the
window), mean: how far the queue coalesces."""

import numpy as np

from bench.lib import spans

UNIT, BETTER, SOURCE = "ops", "higher", "program_counter"
LAYER = "work queue"
MOVES = "suggestions_per_s"


def read(ctx):
    w = spans.window(ctx)
    if w is None:
        return None
    ops = [r.counts["ops"] for r in w.spans
           if r.name == "vizier.lease.wait" and r.counts.get("ops")
           and w.t0_ns <= r.end_ns <= w.t1_ns]
    return float(np.mean(ops)) if ops else None
