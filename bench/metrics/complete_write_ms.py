"""Datastore: the CompleteTrial handler (study lock, trial read and write),
mean per op."""

import numpy as np

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER = "datastore"
MOVES = "suggestions_per_s"


def read(ctx):
    s = ctx.recorder.complete_s
    return float(np.mean(s)) * 1e3 if s else None
