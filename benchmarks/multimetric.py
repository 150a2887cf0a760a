"""`make bench-multimetric`: hypervolume-vs-trials, GP bandit vs NSGA-II.

Runs the multi-metric GP bandit (the DEFAULT policy for multi-objective
studies since schema v4) and the NSGA-II baseline head-to-head on two
synthetic multi-objective problems — sequential suggest/evaluate/complete
loops of ``N_TRIALS`` trials each — and reports the hypervolume of the
observed Pareto frontier at fixed checkpoints against a FIXED, explicit
reference point (never the data-derived one: both algorithms must be scored
in the same box).

Problems (unit square inputs, larger-is-better objectives):
  * branin2d-ish "two peaks" (k=2): m_j = -||x - c_j||², competing optima at
    c_1 = (0.2, 0.7) and c_2 = (0.8, 0.3); the Pareto set is the segment
    between the peaks.
  * "three peaks" (k=3): same construction with three competing centers;
    hypervolume via the Monte-Carlo estimator (k >= 3).

Floor (asserted PASS/FAIL, mirrored in the acceptance criteria): the GP
bandit's hypervolume at ``N_TRIALS`` completed trials must be >= NSGA-II's
on BOTH problems. The model-based policy should buy its fit cost back in
sample efficiency at expensive-evaluation trial counts; if it cannot even
match the evolutionary baseline, the scalarized acquisition regressed.

Writes ``BENCH_multimetric.json`` so the trajectory is machine-readable
from this PR onward.
"""

import argparse
import json
import os

import numpy as np

from benchmarks.bench_util import emit

from repro import compile_cache
from repro.core import Measurement, StudyConfig, Trial
from repro.core.pareto import hypervolume, pareto_frontier_indices
from repro.core.study import Study
from repro.pythia.policy import StudyDescriptor, SuggestRequest
from repro.pythia.registry import make_policy
from repro.pythia.supporter import DatastorePolicySupporter
from repro.service.datastore import InMemoryDatastore

N_TRIALS = 50
CHECKPOINTS = (10, 25, 50)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(_ROOT, "BENCH_multimetric.json")

# Objective values are bounded below by -(the squared diameter of the unit
# square) = -2; the reference point sits below every achievable value so
# frontier growth anywhere is rewarded, and is shared by both algorithms.
REF_VALUE = -2.1

PROBLEMS = {
    "two-peaks-k2": [(0.2, 0.7), (0.8, 0.3)],
    "three-peaks-k3": [(0.2, 0.7), (0.8, 0.3), (0.5, 0.95)],
}


def _objectives(centers, x0: float, x1: float) -> dict:
    return {
        f"m{j}": -((x0 - cx) ** 2 + (x1 - cy) ** 2)
        for j, (cx, cy) in enumerate(centers)
    }


def _config(centers, algorithm: str) -> StudyConfig:
    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    root.add_float_param("x0", 0.0, 1.0)
    root.add_float_param("x1", 0.0, 1.0)
    for j in range(len(centers)):
        cfg.metrics.add(f"m{j}", "MAXIMIZE")
    cfg.algorithm = algorithm
    return cfg


def run_loop(problem: str, algorithm: str) -> dict:
    """One sequential optimization loop; hypervolume at each checkpoint."""
    centers = PROBLEMS[problem]
    k = len(centers)
    cfg = _config(centers, algorithm)
    ds = InMemoryDatastore()
    study = Study(name=f"owners/bench/studies/mm-{problem}-{algorithm}",
                  study_config=cfg)
    ds.create_study(study)
    supporter = DatastorePolicySupporter(ds, study.name)
    policy = make_policy(algorithm, supporter, cfg)
    ref = np.full((k,), REF_VALUE)
    ys = []
    hv_at = {}
    for i in range(N_TRIALS):
        config = ds.get_study(study.name).study_config  # fresh metadata
        decision = policy.suggest(SuggestRequest(
            study_descriptor=StudyDescriptor(config=config, guid=study.name),
            count=1))
        params = decision.suggestions[0].parameters
        x0 = params["x0"].as_float
        x1 = params["x1"].as_float
        metrics = _objectives(centers, x0, x1)
        t = Trial(parameters={"x0": x0, "x1": x1})
        t.complete(Measurement(metrics=metrics))
        ds.create_trial(study.name, t)
        ys.append([metrics[f"m{j}"] for j in range(k)])
        if (i + 1) in CHECKPOINTS:
            y = np.asarray(ys)
            front = y[pareto_frontier_indices(y)]
            hv_at[i + 1] = float(hypervolume(front, ref))
    return {"problem": problem, "algorithm": algorithm, "k": k,
            "hv_at": hv_at}


def main() -> int:
    compile_cache.enable()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default=OUT_PATH)
    args = parser.parse_args()

    runs = []
    floors = []
    for problem in PROBLEMS:
        gp = run_loop(problem, "DEFAULT")
        nsga = run_loop(problem, "NSGA2")
        runs += [gp, nsga]
        gp_hv = gp["hv_at"][N_TRIALS]
        nsga_hv = nsga["hv_at"][N_TRIALS]
        ok = gp_hv >= nsga_hv
        floors.append(ok)
        emit(f"multimetric.{problem}.hv_at_{N_TRIALS}", gp_hv * 1e6,
             f"gp_hv={gp_hv:.4f} nsga_hv={nsga_hv:.4f} "
             f"{'PASS' if ok else 'FAIL'}")

    verdict = "PASS" if all(floors) else "FAIL"
    payload = {
        "bench": "multimetric",
        "unit": f"hypervolume at trial checkpoints {list(CHECKPOINTS)} "
                f"(fixed reference point {REF_VALUE} per metric)",
        "floors": {f"gp_hv_ge_nsga_hv_at_{N_TRIALS}": True},
        "runs": runs,
        "verdict": verdict,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out} verdict={verdict}")
    return 0 if verdict == "PASS" else 1


if __name__ == "__main__":
    raise SystemExit(main())
