"""Datastore: time a worker batch spends in study and trial reads, per
suggest op it serves."""

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER = "datastore"
MOVES = "suggestions_per_s"


def read(ctx):
    v = ctx.per_served_op(sum(b.read_s for b in ctx.recorder.batches))
    return None if v is None else v * 1e3
