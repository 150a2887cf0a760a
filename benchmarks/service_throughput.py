"""Paper Figure 2: distributed pipeline throughput + crash recovery time.

suggestions/sec and RPC latency vs #concurrent clients, plus the time for a
freshly-restarted server (same durable datastore) to recover pending ops.

``--batched`` additionally runs the batched-suggestion scenario: the same
per-(study, client) workload issued through BatchSuggestTrials /
BatchCompleteTrials (one RPC + one coalesced Pythia dispatch per round)
instead of one thread + one SuggestTrials poll-loop per client, at 1, 8 and
64 concurrent clients.
"""

import argparse
import threading
import time

from benchmarks.bench_util import emit

from repro import compile_cache
from repro.core import Measurement, ScaleType, StudyConfig, Trial
from repro.service import (
    DefaultVizierServer,
    DistributedVizierServer,
    VizierBatchClient,
    VizierClient,
)
from repro.service.datastore import SQLiteDatastore
from repro.service.vizier_service import VizierService


def _config() -> StudyConfig:
    cfg = StudyConfig()
    cfg.search_space.select_root().add_float_param("x", 0, 1,
                                                   scale_type=ScaleType.LINEAR)
    cfg.metrics.add("obj", "MAXIMIZE")
    cfg.algorithm = "RANDOM_SEARCH"
    return cfg


def bench_throughput(n_clients: int, n_trials: int = 12) -> None:
    server = DefaultVizierServer()
    seed = VizierClient.load_or_create_study(
        f"tput-{n_clients}", _config(), client_id="seed", target=server.address)
    latencies, errs = [], []
    lock = threading.Lock()

    def worker(wid):
        try:
            c = VizierClient(server.address, seed.study_name, f"w{wid}")
            for _ in range(n_trials):
                t0 = time.perf_counter()
                (t,) = c.get_suggestions(count=1)
                c.complete_trial({"obj": 0.1}, trial_id=t.id)
                with lock:
                    latencies.append(time.perf_counter() - t0)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    assert not errs, errs
    total = n_clients * n_trials
    latencies.sort()
    p50 = latencies[len(latencies) // 2] * 1e3
    p95 = latencies[int(len(latencies) * 0.95)] * 1e3
    emit(f"fig2.throughput.clients={n_clients}", wall / total * 1e6,
         f"trials_per_sec={total/wall:.1f} p50={p50:.1f}ms p95={p95:.1f}ms")
    server.stop()


def bench_batched_throughput(n_clients: int, n_rounds: int = 12) -> None:
    """suggestions/sec with server-side coalescing: each round is ONE
    BatchSuggestTrials RPC covering every (study, client) pair, then ONE
    BatchCompleteTrials for the evaluations."""
    server = DefaultVizierServer()
    studies = []
    for i in range(n_clients):
        c = VizierClient.load_or_create_study(
            f"btput-{n_clients}-{i}", _config(), client_id="seed",
            target=server.address)
        studies.append(c.study_name)
        c.close()

    batch = VizierBatchClient(server.address)
    requests = [
        {"study_name": s, "client_id": f"w{i}", "count": 1}
        for i, s in enumerate(studies)
    ]
    latencies = []
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        r0 = time.perf_counter()
        per_req = batch.get_suggestions(requests)
        batch.complete_trials([
            {"study_name": s, "trial_name": f"{s}/trials/{trials[0].id}",
             "metrics": {"obj": 0.1}}
            for s, trials in zip(studies, per_req)
        ])
        latencies.append(time.perf_counter() - r0)
    wall = time.perf_counter() - t0
    total = n_clients * n_rounds
    latencies.sort()
    p50 = latencies[len(latencies) // 2] * 1e3
    p95 = latencies[int(len(latencies) * 0.95)] * 1e3
    emit(f"fig2.batched_throughput.clients={n_clients}", wall / total * 1e6,
         f"suggestions_per_sec={total/wall:.1f} round_p50={p50:.1f}ms "
         f"round_p95={p95:.1f}ms")
    batch.close()
    server.stop()


def bench_remote_pythia(n_clients: int, n_rounds: int = 10,
                        n_seed_trials: int = 200) -> float:
    """Figure-2 topology (separate Pythia service): coalesced
    PythiaBatchSuggest vs the per-study PythiaSuggest baseline.

    Each round is one BatchSuggestTrials covering every (study, client)
    pair. The baseline forwards that batch to the Pythia service one study
    at a time with the pre-batch wire pattern (each PythiaSuggest re-fetches
    the study and the full trial list for max_trial_id, then the policy
    re-fetches per state); the coalesced path ships the whole work-list in
    one PythiaBatchSuggest frame backed by a single
    GetTrialsMulti(include_studies) prefetch shared by every policy.
    Returns the coalesced/baseline suggestions-per-sec ratio.
    """
    rates = {}
    for coalesce in (False, True):
        server = DistributedVizierServer(coalesce_remote=coalesce,
                                         pythia_single_fetch=coalesce)
        studies = []
        for i in range(n_clients):
            c = VizierClient.load_or_create_study(
                f"rmt-{coalesce}-{n_clients}-{i}", _config(), client_id="seed",
                target=server.address)
            for j in range(n_seed_trials):  # realistic trial payloads
                t = Trial(parameters={"x": (j + 1) / (n_seed_trials + 1)})
                t.complete(Measurement(metrics={"obj": 0.1 * j}))
                c.add_trial(t)
            studies.append(c.study_name)
            c.close()

        batch = VizierBatchClient(server.address, poll_interval=0.001)
        requests = [
            {"study_name": s, "client_id": f"w{i}", "count": 1}
            for i, s in enumerate(studies)
        ]
        t0 = time.perf_counter()
        for r in range(n_rounds):
            per_req = batch.get_suggestions(requests)
            batch.complete_trials([
                {"trial_name": f"{s}/trials/{trials[0].id}",
                 "metrics": {"obj": 0.1}}
                for s, trials in zip(studies, per_req)
            ])
        wall = time.perf_counter() - t0
        total = n_clients * n_rounds
        rates[coalesce] = total / wall
        label = "coalesced" if coalesce else "per_study_rpc"
        emit(f"fig2.remote_pythia.{label}.clients={n_clients}",
             wall / total * 1e6, f"suggestions_per_sec={total/wall:.1f}")
        batch.close()
        server.stop()
    ratio = rates[True] / rates[False]
    emit(f"fig2.remote_pythia.speedup.clients={n_clients}", ratio,
         f"coalesced_vs_per_study_rpc={ratio:.2f}x")
    return ratio


def _gp_config() -> StudyConfig:
    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    root.add_float_param("x", 0, 1, scale_type=ScaleType.LINEAR)
    root.add_float_param("y", 0, 1, scale_type=ScaleType.LINEAR)
    cfg.metrics.add("obj", "MAXIMIZE")
    cfg.algorithm = "GP_UCB"
    return cfg


def bench_warm_start(trial_counts=(50, 200, 500), n_repeats=7) -> None:
    """Warm-started GP-bandit suggest (persisted PolicyState, paper §6.3) vs
    the cold per-operation refit, at fixed completed-trial counts.

    Each operation constructs a fresh policy (the stateless Pythia lifespan)
    against the same datastore; the warm scenario keeps the persisted
    ``repro.gp_bandit`` checkpoint between operations, the cold scenario
    wipes it first. Reports median fit wall-time and suggest latency, plus
    the warm-vs-cold fit speedup.
    """
    from repro.core.study import Study
    from repro.pythia.gp_bandit import GPBanditPolicy
    from repro.pythia.policy import StudyDescriptor, SuggestRequest
    from repro.pythia.state import GP_BANDIT_NAMESPACE
    from repro.pythia.supporter import DatastorePolicySupporter
    from repro.service.datastore import InMemoryDatastore

    med = lambda xs: sorted(xs)[len(xs) // 2]
    for n in trial_counts:
        ds = InMemoryDatastore()
        study = Study(name=f"owners/bench/studies/warm-{n}",
                      study_config=_gp_config())
        ds.create_study(study)
        for i in range(n):  # deterministic smooth objective
            x = (i + 1) / (n + 1)
            y = ((i * 7919) % n) / n
            t = Trial(parameters={"x": x, "y": y})
            t.complete(Measurement(
                metrics={"obj": -(x - 0.37) ** 2 - 0.5 * (y - 0.61) ** 2}))
            ds.create_trial(study.name, t)
        supporter = DatastorePolicySupporter(ds, study.name)

        def one_suggest():
            config = ds.get_study(study.name).study_config  # fresh metadata
            policy = GPBanditPolicy(supporter)
            t0 = time.perf_counter()
            policy.suggest(SuggestRequest(
                study_descriptor=StudyDescriptor(config=config, guid=study.name),
                count=1))
            return time.perf_counter() - t0, policy

        def wipe_state():
            s = ds.get_study(study.name)
            s.study_config.metadata.clear_ns(GP_BANDIT_NAMESPACE)
            ds.update_study(s)

        # cold scenario: state wiped before every op (first run untimed: jit)
        wipe_state()
        one_suggest()
        cold_fit, cold_wall = [], []
        for _ in range(n_repeats):
            wipe_state()
            wall, policy = one_suggest()
            assert not policy.last_fit_warm
            cold_wall.append(wall)
            cold_fit.append(policy.last_fit_seconds)
        # warm scenario: checkpoint persists; two untimed ops let the resumed
        # trajectory reach the convergence exit (as a live study would)
        wipe_state()
        one_suggest()
        one_suggest()
        warm_fit, warm_wall = [], []
        for _ in range(n_repeats):
            wall, policy = one_suggest()
            assert policy.last_fit_warm
            warm_wall.append(wall)
            warm_fit.append(policy.last_fit_seconds)

        emit(f"warmstart.n={n}.cold", med(cold_fit) * 1e6,
             f"median_fit_ms={med(cold_fit)*1e3:.2f} "
             f"suggest_ms={med(cold_wall)*1e3:.2f}")
        emit(f"warmstart.n={n}.warm", med(warm_fit) * 1e6,
             f"median_fit_ms={med(warm_fit)*1e3:.2f} "
             f"suggest_ms={med(warm_wall)*1e3:.2f}")
        ratio = med(cold_fit) / max(med(warm_fit), 1e-9)
        verdict = "PASS" if n < 200 or ratio >= 2.0 else "FAIL"
        emit(f"warmstart.n={n}.fit_speedup", ratio,
             f"warm_vs_cold={ratio:.1f}x (floor 2x at n>=200) {verdict}")


def bench_transfer(n_prior_trials=60, shift=0.07, tol=0.01, max_trials=25,
                   n_repeats=3) -> None:
    """Transfer learning (stacked residual GP over prior studies) vs a cold
    study, on a shifted-objective family: trials-to-target and the
    suggestion-latency overhead the prior stack adds.

    A prior study is seeded with ``n_prior_trials`` evaluations of the base
    objective; the target study optimizes the same family with its optimum
    shifted by ``shift``. Target reached when the best observed value is
    within ``tol`` of the optimum (0.0). The transfer study must reach it in
    no more trials than the cold study (floor, asserted PASS/FAIL).
    """
    import numpy as np

    def objective(params, s):
        x, y = float(params["x"]), float(params["y"])
        return -((x - (0.30 + s)) ** 2) - 0.5 * ((y - (0.60 - s)) ** 2)

    server = DefaultVizierServer()
    prior = VizierClient.load_or_create_study(
        "xfer-prior", _gp_config(), client_id="seed", target=server.address)
    rng = np.random.RandomState(0)
    for _ in range(n_prior_trials):
        p = {"x": float(rng.rand()), "y": float(rng.rand())}
        t = Trial(parameters=p)
        t.complete(Measurement(metrics={"obj": objective(p, 0.0)}))
        prior.add_trial(t)

    def run_to_target(tag, priors):
        trials_used, suggest_ms = [], []
        for rep in range(n_repeats):
            c = VizierClient.load_or_create_study(
                f"xfer-{tag}-{rep}", _gp_config(), client_id="w",
                target=server.address, prior_studies=priors)
            best, used = float("-inf"), max_trials
            for i in range(1, max_trials + 1):
                t0 = time.perf_counter()
                (t,) = c.get_suggestions(count=1)
                suggest_ms.append((time.perf_counter() - t0) * 1e3)
                val = objective(t.parameters.as_dict(), shift)
                c.complete_trial({"obj": val}, trial_id=t.id)
                best = max(best, val)
                if best >= -tol:
                    used = i
                    break
            trials_used.append(used)
            c.close()
        med = lambda xs: sorted(xs)[len(xs) // 2]
        return med(trials_used), med(suggest_ms)

    cold_trials, cold_ms = run_to_target("cold", None)
    xfer_trials, xfer_ms = run_to_target("warm", [prior.study_name])
    emit("transfer.cold.trials_to_target", cold_trials,
         f"median over {n_repeats} runs, suggest_p50={cold_ms:.1f}ms")
    emit("transfer.stacked.trials_to_target", xfer_trials,
         f"median over {n_repeats} runs, suggest_p50={xfer_ms:.1f}ms")
    verdict = "PASS" if xfer_trials <= cold_trials else "FAIL"
    emit("transfer.trials_saved", cold_trials - xfer_trials,
         f"cold={cold_trials} transfer={xfer_trials} "
         f"latency_overhead={xfer_ms - cold_ms:+.1f}ms {verdict}")
    prior.close()
    server.stop()


def bench_crash_recovery(tmpdir="/tmp/bench_crash.db") -> None:
    import os

    if os.path.exists(tmpdir):
        os.remove(tmpdir)
    ds = SQLiteDatastore(tmpdir)
    svc = VizierService(ds)
    client = VizierClient.load_or_create_study("crash", _config(),
                                               client_id="c", target=svc)
    (t,) = client.get_suggestions(count=1)  # normal op committed
    # enqueue an op that the "crashing" server never finishes
    import repro.service.operations as ops_lib

    op = ops_lib.new_suggest_operation(client.study_name, "c2", 1)
    ds.put_operation(op)
    svc.shutdown()  # crash

    t0 = time.perf_counter()
    svc2 = VizierService(SQLiteDatastore(tmpdir))
    n = svc2.recover_pending_operations()
    deadline = time.time() + 30
    while time.time() < deadline:
        if svc2._ds.get_operation(op["name"])["done"]:
            break
        time.sleep(0.01)
    recovery = (time.perf_counter() - t0) * 1e6
    assert svc2._ds.get_operation(op["name"])["done"]
    emit("fig2.crash_recovery", recovery, f"recovered_ops={n} PASS")
    svc2.shutdown()


def main() -> None:
    compile_cache.enable()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--batched", action="store_true",
                        help="run the BatchSuggestTrials coalescing scenario")
    parser.add_argument("--remote-pythia", action="store_true",
                        help="run the Figure-2 remote-Pythia scenario "
                             "(coalesced vs per-study-RPC dispatch)")
    parser.add_argument("--warm-start", action="store_true",
                        help="run the warm-started GP-bandit scenario "
                             "(persisted PolicyState vs cold refit)")
    parser.add_argument("--transfer", action="store_true",
                        help="run the transfer-learning scenario (stacked "
                             "residual GP over a prior study vs cold, "
                             "trials-to-target on a shifted objective)")
    args = parser.parse_args()
    if args.batched:
        for n in (1, 8, 64):
            bench_batched_throughput(n)
        return
    if args.remote_pythia:
        for n in (1, 8, 64):
            bench_remote_pythia(n)
        return
    if args.warm_start:
        bench_warm_start()
        return
    if args.transfer:
        bench_transfer()
        return
    for n in (1, 4, 16):
        bench_throughput(n)
    bench_crash_recovery()


if __name__ == "__main__":
    main()
