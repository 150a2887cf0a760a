"""Suggestions in the workers' hands by the window's end, per second of it."""

UNIT, BETTER, SOURCE, LAYER, MOVES = ("suggestions/s", "higher", "host_clock",
                                      None, None)


def read(ctx):
    end = ctx.t0 + ctx.seconds
    n = sum(len(r.trials) for r in ctx.suggest_ops() if r.done <= end)
    return n / ctx.seconds
