"""Distributed fault-tolerant tuning (paper §5): parallel workers train real
(reduced) JAX models, stream learning curves, survive crashes, share a study.

Demonstrates, end to end:
  * N parallel TuningWorkers on one study (parallel trials);
  * a worker "crash" mid-trial + restart with the same client_id -> the
    service re-issues the SAME trial (client-side fault tolerance);
  * median automated stopping on learning curves;
  * the separate-Pythia-service topology (paper Figure 2);
  * batched suggestions: one BatchSuggestTrials RPC drives many
    (study, client) pairs through a single coalesced Pythia dispatch.

    PYTHONPATH=src python examples/distributed_tuning.py
"""

import sys
import threading

sys.path.insert(0, "src")

from repro import compile_cache
from repro.configs import get_arch
from repro.core import AutomatedStoppingConfig, ScaleType, StudyConfig, TrialState
from repro.service import DistributedVizierServer, VizierBatchClient, VizierClient
from repro.train.data import DataConfig
from repro.tuning import TuningTask, TuningWorker


def make_study_config() -> StudyConfig:
    config = StudyConfig()
    root = config.search_space.select_root()
    root.add_float_param("peak_lr", 1e-4, 3e-2, scale_type=ScaleType.LOG)
    root.add_float_param("weight_decay", 0.0, 0.3)
    config.metrics.add("loss", goal="MINIMIZE")
    config.algorithm = "GP_UCB"
    config.automated_stopping = (
        AutomatedStoppingConfig.median_automated_stopping_config(
            min_completed_trials=2))
    return config


def main():
    compile_cache.enable()
    server = DistributedVizierServer()  # API service + separate Pythia service
    print(f"API server: {server.address}; Pythia server: {server.pythia_address}")

    arch = get_arch("phi4_mini_3p8b", reduced=True)
    task = TuningTask(
        arch=arch,
        data=DataConfig(vocab_size=arch.vocab_size, seq_len=64, global_batch=8),
        total_steps=30,
        report_every=5,
    )

    client = VizierClient.load_or_create_study(
        "lm-tuning", make_study_config(), client_id="admin",
        target=server.address)

    # --- fault-tolerance demo: worker pulls a trial then "crashes" ----------
    w0 = TuningWorker(server.address, client.study_name, "worker_0", task)
    (trial_before,) = w0.client.get_suggestions(count=1)
    print(f"worker_0 got trial {trial_before.id}, then crashes mid-evaluation...")
    del w0  # crash: no CompleteTrial ever sent

    w0b = TuningWorker(server.address, client.study_name, "worker_0", task)
    (trial_after,) = w0b.client.get_suggestions(count=1)
    assert trial_after.id == trial_before.id, "client_id rebind failed!"
    print(f"restarted worker_0 got the SAME trial {trial_after.id} back ✓")

    # --- parallel workers ----------------------------------------------------
    workers = [w0b] + [
        TuningWorker(server.address, client.study_name, f"worker_{i}", task)
        for i in (1, 2)
    ]
    threads = [threading.Thread(target=w.run, kwargs={"max_trials": 2})
               for w in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    trials = client.list_trials(states=[TrialState.COMPLETED])
    best = client.list_optimal_trials()
    print(f"\ncompleted {len(trials)} trials across 3 workers")
    for t in sorted(trials, key=lambda t: t.id):
        print(f"  trial {t.id} [{t.client_id}]: "
              f"lr={t.parameters['peak_lr'].as_float:.5f} "
              f"-> loss {t.final_objective('loss'):.4f} "
              f"({len(t.measurements)} intermediate reports)")
    if best:
        print(f"best: trial {best[0].id} loss={best[0].final_objective('loss'):.4f}")

    # --- batched suggestions -------------------------------------------------
    # A scheduler coordinating many workers (or many studies) can ask the
    # server to coalesce all of their suggestion work into ONE Pythia
    # dispatch: one RPC out, one policy invocation per study with the summed
    # count, pipelined operation polling back. Same protocol semantics as N
    # individual SuggestTrials calls (client_id binding included) at a
    # fraction of the round trips — see benchmarks/service_throughput.py
    # --batched for suggestions/sec at 1/8/64 concurrent clients.
    batch = VizierBatchClient(server.address)
    per_worker = batch.get_suggestions([
        {"study_name": client.study_name, "client_id": f"batch_w{i}", "count": 1}
        for i in range(4)
    ])
    print(f"\nbatched: 1 RPC -> {sum(len(r) for r in per_worker)} trials "
          f"across {len(per_worker)} workers "
          f"(ids {[t.id for r in per_worker for t in r]})")
    batch.complete_trials([
        {"trial_name": f"{client.study_name}/trials/{r[0].id}",
         "metrics": {"loss": 1.0 + 0.1 * i}}
        for i, r in enumerate(per_worker)
    ])
    print("batched: all 4 evaluations reported in one BatchCompleteTrials RPC")
    batch.close()
    server.stop()


if __name__ == "__main__":
    main()
