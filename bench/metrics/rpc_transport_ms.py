"""Service tier: RPC transport per suggest op served: over the op's frames
(its SuggestTrials and each WaitOperation), the client's ``vizier.rpc.call``
less the server's ``vizier.rpc.dispatch`` of the same request id, summed;
the mean over the served ops whose SuggestTrials frame lies in the window."""

import numpy as np

from bench.lib import spans

UNIT, BETTER, SOURCE = "ms", "lower", "program_span"
LAYER = "service tier"
MOVES = "suggestions_per_s"


def read(ctx):
    w = spans.window(ctx)
    if w is None:
        return None
    served = set(w.served_op_names())
    dispatch = {r.counts.get("rid"): r for r in w.spans
                if r.name == "vizier.rpc.dispatch" and r.trace_id in served}
    per_op, seen = {}, set()
    for call in w.spans:
        if call.name != "vizier.rpc.call":
            continue
        d = dispatch.get(call.counts.get("rid"))
        if d is None:
            continue
        per_op[d.trace_id] = (per_op.get(d.trace_id, 0)
                              + call.wall_ns - d.wall_ns)
        if d.counts.get("method") == "SuggestTrials":
            seen.add(d.trace_id)
    ms = [per_op[op] * 1e-6 for op in seen]
    return float(np.mean(ms)) if ms else None
