"""Service tier (RPC, long-poll, finalize) per suggest op: the client's
send-to-done time less the queue wait less the policy call, means over the
window."""

import numpy as np

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER = "service tier"
MOVES = "suggestions_per_s"


def read(ctx):
    ops = ctx.suggest_ops()
    waits, policy = ctx.queue_waits_s(), ctx.policy_s_per_op()
    if not ops or not waits or not policy:
        return None
    rpc = np.mean([r.done - r.sent for r in ops])
    return float(rpc - np.mean(waits) - np.mean(policy)) * 1e3
