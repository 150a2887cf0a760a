"""Device: traces and compiles that JAX reported inside the window (the
program's engine TRACE_COUNTS and JAX's own compile events); 0 when every
shape was warmed up."""

UNIT, BETTER, SOURCE = "compiles", "lower", "program_counter"
LAYER = "device"
MOVES = "suggestions_per_s"


def read(ctx):
    return float(ctx.window_compiles)
