"""Kernels: gram's share of its roofline over the window, 100 x the least
time the chip could take for each call (the larger of its operations over
the peak FLOP/s and its least bytes over the HBM bandwidth, from the call's
shapes, bench/roofline/gram.py) summed, over the kernel's device time."""

UNIT, BETTER, SOURCE = "%", "higher", "device_trace"
LAYER = "kernels"
MOVES = "suggestions_per_s.steady"
KERNEL = "gram"


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.roofline_pct(KERNEL, ctx.peaks)
