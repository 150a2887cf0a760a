"""Fit: Adam steps per policy call (the policy's last_fit_steps), mean."""

import numpy as np

UNIT, BETTER, SOURCE = "steps", "lower", "program_counter"
LAYER = "fit"
MOVES = "suggestions_per_s"


def read(ctx):
    calls = ctx.recorder.calls
    return float(np.mean([c.fit_steps for c in calls])) if calls else None
