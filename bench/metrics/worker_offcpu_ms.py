"""Work queue: time a Pythia worker's batch spends off the CPU (its
``vizier.worker.batch`` wall time less the worker thread's CPU time: waits
for the interpreter lock, for locks and for I/O), per suggest op served."""

from bench.lib import spans

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER = "work queue"
MOVES = "suggestions_per_s"


def read(ctx):
    w = spans.window(ctx)
    if w is None:
        return None
    return w.per_served_op_ms(sum(r.wall_ns - r.cpu_ns for r in w.batches))
