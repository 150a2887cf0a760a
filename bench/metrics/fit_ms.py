"""Fit: device time of the Adam-step programs per suggest op served in
the traced span."""

UNIT, BETTER, SOURCE = "ms", "lower", "device_trace"
LAYER = "fit"
MOVES = "suggestions_per_s"
MODULES = ("_fit_step", "_fit_step_metrics", "_neg_mll", "_mll_grad",
           "_neg_mll_metrics")


def read(ctx):
    if ctx.trace is None:
        return None
    v = ctx.per_traced_op(ctx.trace.module_seconds(MODULES))
    return None if not v else v * 1e3
