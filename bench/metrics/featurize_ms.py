"""Policy host work: trials_to_xy, to_features and to_parameters, per
suggest op served."""

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER = "policy host work"
MOVES = "suggestions_per_s"


def read(ctx):
    v = ctx.per_served_op(sum(c.featurize_s for c in ctx.recorder.calls))
    return None if v is None else v * 1e3
