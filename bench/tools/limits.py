#!/usr/bin/env python3
"""Readings for the correctness limits: the program and its control, by seed.

    python3 bench/tools/limits.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--controls high bfloat16]

One process, one chip: for each seed it runs the cell as ``bench/run.py``
does (a short window at the cell's own load and sizes) and prints one JSON
line with the program's numbers (``checks``) and, for each control, the
same numbers with the reference computed at that lower matmul precision in
the program's place (``controls``). The lower reading of a limit is the
largest the program gives over the seeds; the upper is the smallest the
control gives. The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import run  # noqa: E402
from bench.lib import cells  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--controls", nargs="*", default=["high", "bfloat16"])
    args = parser.parse_args()
    cell = cells.load_cell(args.workload)
    run.CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    device = run.device_info(cell.chips)
    for seed in args.seeds:
        res = run.run_cell(cell, seed, args.seconds, False, device,
                           controls=tuple(args.controls))
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": {k: v["value"]
                                     for k, v in res["checks"].items()},
                          "controls": res.get("controls", {}),
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
