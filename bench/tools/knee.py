#!/usr/bin/env python3
"""The knee sweep: the highest offered rate the served path keeps up with.

    python3 bench/tools/knee.py --workload <cell> --seconds <s> \
        --rates <r> [<r> ...] [--seed <n>]

One process, one chip. For each rate (events per second) it runs the cell
as ``bench/run.py`` does with the traffic's rate replaced, and prints one
JSON line: the suggest ops due, those completed by one second past the
window's end, their share, and the latency quantiles. The knee is the
highest rate whose share stays at or above 98%; a cell runs at about 0.8 of
it, and its traffic file records the sweep.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import run  # noqa: E402
from bench.lib import cells, context, plan  # noqa: E402


def sweep(base, rates, seconds, seed, device, grace_s):
    for rate in rates:
        cell = dataclasses.replace(base,
                                   traffic=dict(base.traffic, rate_per_s=rate))
        kept = []
        try:
            res = run.run_cell(cell, seed, seconds, False, device,
                               grace_s=grace_s, sample_calls=2, contexts=kept)
        except plan.PlanError as e:
            yield {"rate": rate, "error": str(e)}
            continue
        ctx = kept[0]
        end = ctx.t0 + ctx.seconds + 1.0
        due = [r for r in ctx.records if r.kind == "suggest"]
        n_due = len(due) + len(ctx.unfinished_due)
        done = sum(1 for r in due if r.ok and r.done <= end)
        lat = ctx.latencies_ms("suggest")
        yield {"rate": rate, "seconds": seconds, "due": n_due,
               "completed": done, "share": done / n_due if n_due else None,
               "p50_ms": context.percentile(lat, 50),
               "p95_ms": context.percentile(lat, 95),
               "suggestions_per_s":
                   res["metrics"]["suggestions_per_s"]["value"],
               "correct": res["correct"]}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rates", type=float, nargs="+", required=True)
    parser.add_argument("--seed", type=int, default=2**31 + 4242)
    parser.add_argument("--grace", type=float, default=20.0)
    args = parser.parse_args()
    base = cells.load_cell(args.workload)
    run.CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    device = run.device_info(base.chips)
    for row in sweep(base, args.rates, args.seconds, args.seed, device,
                     args.grace):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
