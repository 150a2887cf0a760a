"""Service tier: the study-group finalize of a worker batch (transaction,
trial creation, done ops; the ``vizier.finalize`` spans less their
study-lock wait), per suggest op served."""

from bench.lib import spans

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER = "service tier"
MOVES = "suggestions_per_s"


def read(ctx):
    w = spans.window(ctx)
    if w is None:
        return None
    return w.per_served_op_ms(
        sum(w.self_ns(r) for r in w.in_batches("vizier.finalize")))
