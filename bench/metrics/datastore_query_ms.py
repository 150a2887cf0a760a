"""Datastore: self time of the SQLite reads a worker batch makes (the
``vizier.datastore.query`` spans: execute and fetch, less the wait for the
connection lock), per suggest op served."""

from bench.lib import spans

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER = "datastore"
MOVES = "suggestions_per_s"


def read(ctx):
    w = spans.window(ctx)
    if w is None:
        return None
    return w.per_served_op_ms(
        sum(w.self_ns(r) for r in w.in_batches("vizier.datastore.query")))
