"""Service tier: waits for the study lock on the suggest path (the
``vizier.lock.wait`` spans under ``vizier.suggest.prepare`` and
``vizier.finalize`` that started in the window), per suggest op served."""

from bench.lib import spans

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER = "service tier"
MOVES = "suggestions_per_s"
PARENTS = ("vizier.suggest.prepare", "vizier.finalize")


def read(ctx):
    w = spans.window(ctx)
    if w is None:
        return None
    waits = [r for r in w.started("vizier.lock.wait")
             if r.parent_id in w.by_id
             and w.by_id[r.parent_id].name in PARENTS]
    return w.per_served_op_ms(sum(r.wall_ns for r in waits))
