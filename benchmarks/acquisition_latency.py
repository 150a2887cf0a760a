"""`make bench-acquisition`: suggest-op latency, engine vs pre-engine path.

Measures median GP-bandit suggest-operation wall time in the STEADY-STATE
SERVING regime at n in {50, 300, 1000} completed trials x count in {1, 8}
batch members: every measured round first lands one newly completed trial
(as a live study does between operations), then times one suggest op per
path against the identical datastore state. The growing trial count is the
point — it is exactly what made the pre-engine acquisition retrace its
jitted ``_ucb``/``_posterior`` kernels on every operation (each distinct
(n_trials, pool) shape recompiles) on top of refactorizing K(X, X) once per
batch member; the engine's bucket-padded shapes absorb the growth with zero
recompiles and one Cholesky + rank-1 appends per op.

Paths: the factorized-posterior engine (default) vs the pre-engine
acquisition kept in-tree (``GPBanditPolicy(use_engine=False)``). Both run
warm-started (persisted PolicyState) on the same study.

Emits one line per scenario plus the speedup, and writes the whole run to
``BENCH_acquisition.json`` so the perf trajectory is machine-readable from
this PR onward.

Large-n regime: above ``SPARSE_THRESHOLD`` completed trials the engine
switches to the SGPR inducing-point posterior (Pallas/XLA triangular-solve +
cholupdate kernels against the m×m inducing factor), so n=5000 runs
ENGINE-ONLY — the pre-engine path at that scale refactorizes an n×n
Cholesky per batch member and is not a serving configuration.

Floors (asserted PASS/FAIL, mirrored in the acceptance criteria):
  * >= 5x median suggest-op speedup at n=300, count=8
  * no regression at n=50, count=1 (engine <= 1.15x of the baseline)
  * <= 100 ms median suggest op at n=5000, count=1 (sparse path)
"""

import argparse
import json
import os
import time

from benchmarks.bench_util import emit

from repro import compile_cache
from repro.core import Measurement, ScaleType, StudyConfig, Trial
from repro.core.study import Study
from repro.pythia.gp_bandit import GPBanditPolicy
from repro.pythia.policy import StudyDescriptor, SuggestRequest
from repro.pythia.supporter import DatastorePolicySupporter
from repro.service.datastore import InMemoryDatastore

SPEEDUP_FLOOR = 5.0          # at n=300, count=8
REGRESSION_CEILING = 1.15    # at n=50, count=1
SPARSE_FLOOR_MS = 100.0      # at n=5000, count=1 (engine-only, sparse path)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_PATH = os.path.join(_ROOT, "BENCH_acquisition.json")


def _config() -> StudyConfig:
    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    root.add_float_param("x", 0, 1, scale_type=ScaleType.LINEAR)
    root.add_float_param("y", 0, 1, scale_type=ScaleType.LINEAR)
    cfg.metrics.add("obj", "MAXIMIZE")
    cfg.algorithm = "GP_UCB"
    return cfg


def _add_trial(ds, study, i: int, n: int) -> None:
    x = (i * 0.6180339887) % 1.0
    y = ((i * 7919) % max(n, 2)) / max(n, 2)
    t = Trial(parameters={"x": x, "y": y})
    t.complete(Measurement(
        metrics={"obj": -(x - 0.37) ** 2 - 0.5 * (y - 0.61) ** 2}))
    ds.create_trial(study.name, t)


def _seeded_study(n: int, count: int):
    ds = InMemoryDatastore()
    study = Study(name=f"owners/bench/studies/acq-{n}-{count}",
                  study_config=_config())
    ds.create_study(study)
    for i in range(n):
        _add_trial(ds, study, i, n)
    return ds, study


def _median(xs):
    return sorted(xs)[len(xs) // 2]


def bench_scenario(n: int, count: int, *, repeats: int, warmup: int) -> dict:
    """Median suggest-op wall per path, live-serving regime.

    Each round lands one newly completed trial, then times one op per path
    at the identical datastore state — so the pre-engine path pays what it
    really paid in production (a fresh (n_trials, pool) shape every op ->
    retrace + per-member refactorization) while the engine stays inside its
    shape bucket. Paths alternate within a round for a paired comparison.
    """
    ds, study = _seeded_study(n, count)
    supporter = DatastorePolicySupporter(ds, study.name)

    def run(use_engine: bool) -> float:
        config = ds.get_study(study.name).study_config  # fresh metadata
        policy = GPBanditPolicy(supporter, use_engine=use_engine)
        t0 = time.perf_counter()
        decision = policy.suggest(SuggestRequest(
            study_descriptor=StudyDescriptor(config=config, guid=study.name),
            count=count))
        assert len(decision.suggestions) == count
        return time.perf_counter() - t0

    engine_s, pre_engine_s = [], []
    for r in range(warmup + repeats):
        _add_trial(ds, study, n + r, n)  # the study grows between ops
        te = run(True)
        tl = run(False)
        if r >= warmup:  # warmup rounds settle the warm-started fit
            engine_s.append(te)
            pre_engine_s.append(tl)
    results = {"engine": _median(engine_s), "pre_engine": _median(pre_engine_s)}
    speedup = results["pre_engine"] / max(results["engine"], 1e-9)
    emit(f"acquisition.n={n}.count={count}", results["engine"] * 1e6,
         f"engine_ms={results['engine']*1e3:.1f} "
         f"pre_engine_ms={results['pre_engine']*1e3:.1f} "
         f"speedup={speedup:.2f}x")
    return {"n": n, "count": count,
            "engine_ms": results["engine"] * 1e3,
            "pre_engine_ms": results["pre_engine"] * 1e3,
            "speedup": speedup}


def bench_sparse_scenario(n: int, count: int, *, repeats: int,
                          warmup: int) -> dict:
    """Median ENGINE-ONLY suggest-op wall at large n (sparse posterior).

    Same live-serving regime as ``bench_scenario`` (one completion lands
    between ops) without the pre-engine baseline: at this scale the
    pre-engine path refactorizes the full n×n Cholesky per batch member and
    is not something anyone serves. Asserts the op actually took the sparse
    path."""
    ds, study = _seeded_study(n, count)
    supporter = DatastorePolicySupporter(ds, study.name)
    policy = GPBanditPolicy(supporter)

    samples = []
    for r in range(warmup + repeats):
        _add_trial(ds, study, n + r, n)
        config = ds.get_study(study.name).study_config  # fresh metadata
        t0 = time.perf_counter()
        decision = policy.suggest(SuggestRequest(
            study_descriptor=StudyDescriptor(config=config, guid=study.name),
            count=count))
        wall = time.perf_counter() - t0
        assert len(decision.suggestions) == count
        assert policy.last_sparse, "n=%d op did not take the sparse path" % n
        if r >= warmup:
            samples.append(wall)
    med_ms = _median(samples) * 1e3
    emit(f"acquisition.sparse.n={n}.count={count}", med_ms * 1e3,
         f"engine_ms={med_ms:.1f} (sparse inducing-point path)")
    return {"n": n, "count": count, "engine_ms": med_ms,
            "pre_engine_ms": None, "speedup": None, "sparse": True}


def main() -> int:
    compile_cache.enable()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument("--warmup", type=int, default=2)
    parser.add_argument("--out", default=OUT_PATH)
    args = parser.parse_args()

    scenarios = []
    for n in (50, 300, 1000):
        for count in (1, 8):
            scenarios.append(bench_scenario(n, count, repeats=args.repeats,
                                            warmup=args.warmup))
    scenarios.append(bench_sparse_scenario(5000, 1, repeats=args.repeats,
                                           warmup=args.warmup))

    by_key = {(s["n"], s["count"]): s for s in scenarios}
    hot = by_key[(300, 8)]
    small = by_key[(50, 1)]
    sparse = by_key[(5000, 1)]
    hot_pass = hot["speedup"] >= SPEEDUP_FLOOR
    small_pass = small["engine_ms"] <= small["pre_engine_ms"] * REGRESSION_CEILING
    sparse_pass = sparse["engine_ms"] <= SPARSE_FLOOR_MS
    verdict = "PASS" if (hot_pass and small_pass and sparse_pass) else "FAIL"
    emit("acquisition.floor.n=300.count=8", hot["speedup"],
         f"speedup={hot['speedup']:.2f}x (floor {SPEEDUP_FLOOR}x) "
         f"{'PASS' if hot_pass else 'FAIL'}")
    emit("acquisition.floor.n=50.count=1",
         small["engine_ms"] / max(small["pre_engine_ms"], 1e-9),
         f"engine/pre_engine={small['engine_ms']/small['pre_engine_ms']:.2f} "
         f"(ceiling {REGRESSION_CEILING}) {'PASS' if small_pass else 'FAIL'}")
    emit("acquisition.floor.n=5000.count=1", sparse["engine_ms"],
         f"engine_ms={sparse['engine_ms']:.1f} (floor {SPARSE_FLOOR_MS}ms) "
         f"{'PASS' if sparse_pass else 'FAIL'}")

    payload = {
        "bench": "acquisition_latency",
        "unit": "ms per suggest operation (median, warm-started)",
        "floors": {"speedup_n300_count8": SPEEDUP_FLOOR,
                   "regression_ceiling_n50_count1": REGRESSION_CEILING,
                   "sparse_ms_n5000_count1": SPARSE_FLOOR_MS},
        "scenarios": scenarios,
        "verdict": verdict,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out} verdict={verdict}")
    return 0 if verdict == "PASS" else 1


if __name__ == "__main__":
    raise SystemExit(main())
