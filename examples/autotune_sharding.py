"""shardtune: Vizier optimizes the framework's own sharding/remat config
against the dry-run roofline (beyond-paper integration).

Full-scale runs go through the 512-device dryrun entrypoint; this example
runs the loop itself on a small in-process mesh so it completes on CPU:

    PYTHONPATH=src python examples/autotune_sharding.py
"""

import sys

sys.path.insert(0, "src")

from repro import compile_cache
from repro.core import TrialState
from repro.service import DefaultVizierServer, VizierClient
from repro.tuning import shardtune_study_config


def fake_roofline(params) -> float:
    """Stands in for tuning.evaluate_cell (which needs the 512-dev process).
    Shape mirrors reality: remat trades memory for compute; chunk sizes trade
    memory for collective efficiency."""
    remat = params["remat"].as_str
    moe_chunks = params["moe_chunks"].as_float
    qc = params["attn_q_chunk"].as_float
    mb = params["num_microbatches"].as_float
    compute = 0.3 * {"none": 1.0, "block": 1.33, "full": 1.6}[remat]
    memory = 0.5 * {"none": 3.0, "block": 1.0, "full": 0.7}[remat] / mb
    collective = 0.4 * (1 + 0.08 * moe_chunks) * (1024.0 / qc) ** 0.25 * mb**0.15
    return max(compute, memory, collective)


def main():
    compile_cache.enable()
    server = DefaultVizierServer()
    config = shardtune_study_config()
    client = VizierClient.load_or_create_study(
        "shardtune-demo", config, client_id="tuner", target=server.address)

    for _ in range(20):
        suggestions = client.get_suggestions(count=1)
        if not suggestions:
            break
        trial = suggestions[0]
        step_time = fake_roofline(trial.parameters)
        client.complete_trial({"step_time_s": step_time}, trial_id=trial.id)

    trials = client.list_trials(states=[TrialState.COMPLETED])
    best = min(trials, key=lambda t: t.final_objective("step_time_s"))
    print(f"explored {len(trials)} configs; best step_time="
          f"{best.final_objective('step_time_s'):.4f}s with "
          f"{best.parameters.as_dict()}")
    server.stop()


if __name__ == "__main__":
    main()
