"""From the first line of bench/run.py to the first due op: start-up, seeding
the studies, the warm-up ops and any compilation."""

UNIT, BETTER, SOURCE, LAYER, MOVES = "s", "lower", "host_clock", None, None


def read(ctx):
    return ctx.t0 - ctx.t_proc
