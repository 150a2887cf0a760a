"""Acquisition: device time of the pool-scoring and append programs per
suggest op served in the traced span."""

UNIT, BETTER, SOURCE = "ms", "lower", "device_trace"
LAYER = "acquisition"
MOVES = "suggestions_per_s"
MODULES = ("_attach_pool", "_append_member", "_append", "_rescore",
           "_pool_scores", "_pool_mean_std", "_query", "_sattach_pool",
           "_sappend_member", "_sappend", "_sappend_rescore", "_squery",
           "_stack_means")


def read(ctx):
    if ctx.trace is None:
        return None
    v = ctx.per_traced_op(ctx.trace.module_seconds(MODULES))
    return None if not v else v * 1e3
