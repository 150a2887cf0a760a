"""Factorization: device time of the factor programs per suggest op served in
the traced span."""

UNIT, BETTER, SOURCE = "ms", "lower", "device_trace"
LAYER = "factorization"
MOVES = "suggestions_per_s"
MODULES = ("_factor", "_alpha", "_sfactor", "_salpha")


def read(ctx):
    if ctx.trace is None:
        return None
    v = ctx.per_traced_op(ctx.trace.module_seconds(MODULES))
    return None if not v else v * 1e3
