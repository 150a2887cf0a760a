"""Work queue: from a suggest op's enqueue to the start of the worker batch
that runs it, mean over the window."""

import numpy as np

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER = "work queue"
MOVES = "suggestions_per_s"


def read(ctx):
    waits = ctx.queue_waits_s()
    return float(np.mean(waits)) * 1e3 if waits else None
