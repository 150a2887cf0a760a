"""Datastore: trials a worker batch decodes (the ``trials`` count of its
``vizier.datastore.decode`` spans), per suggest op served."""

from bench.lib import spans

UNIT, BETTER, SOURCE = "trials", "lower", "program_counter"
LAYER = "datastore"
MOVES = "suggestions_per_s"


def read(ctx):
    w = spans.window(ctx)
    if w is None or not w.served_ops():
        return None
    decoded = sum(int(r.counts.get("trials", 0))
                  for r in w.in_batches("vizier.datastore.decode"))
    return decoded / w.served_ops()
