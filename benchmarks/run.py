"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only features,...]

Output: ``name,us_per_call,derived`` CSV lines per benchmark.
"""

import argparse
import sys
import traceback

from benchmarks import bench_util  # noqa: F401  (sets sys.path)
from repro import compile_cache

MODULES = [
    ("features", "paper Table 1 feature matrix, exercised end-to-end"),
    ("proto_bench", "paper Fig 3 / §4.3 PyVizier<->proto conversion"),
    ("service_throughput", "paper Fig 2 service throughput + crash recovery"),
    ("state_recovery", "paper §6.3 metadata O(1) state restore"),
    ("parallel_tuning", "paper §5 parallel workers + crash rebind"),
    ("kernel_bench", "Pallas kernels (interpret) + analytic FLOPs"),
    ("acquisition_latency",
     "GP-bandit suggest-op latency: posterior engine vs pre-engine path"),
    ("scaleout",
     "Pythia worker-pool throughput scaling + WaitOperation long-poll latency"),
    ("roofline_report", "§Roofline table from dry-run artifacts"),
]


def main() -> None:
    compile_cache.enable()
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help="comma-separated subset of benchmark modules")
    args = p.parse_args()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    for name, desc in MODULES:
        if only and name not in only:
            continue
        print(f"# --- {name}: {desc}", flush=True)
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name}.FAILED,0,", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
