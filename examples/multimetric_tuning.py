"""Multi-metric (Pareto) tuning: accuracy vs latency with the GP bandit.

Multi-objective studies are first-class in the DEFAULT policy: one GP per
metric is fitted on the shared engine buckets and suggestions maximize a
hypervolume-scalarized UCB, so the suggested trials spread ALONG the
accuracy/latency trade-off curve instead of collapsing onto one corner.
The server's ListOptimalTrials returns the observed Pareto frontier, and
the client can score it as a hypervolume number for progress tracking.

    PYTHONPATH=src python examples/multimetric_tuning.py
"""

import sys

sys.path.insert(0, "src")

from repro import compile_cache
from repro.core import ScaleType, StudyConfig
from repro.service import DefaultVizierServer, VizierClient


def evaluate(params) -> dict:
    """Stands in for a train-and-benchmark run. Wider nets are more accurate
    but slower; higher learning rates help up to a point."""
    width = params["width"].as_float
    lr = params["lr"].as_float
    accuracy = (width / 1024.0) ** 0.3 * (1.0 - 8.0 * (lr - 0.02) ** 2)
    latency_ms = 1.5 + (width / 64.0) ** 1.4
    return {"accuracy": accuracy, "latency_ms": latency_ms}


def main():
    compile_cache.enable()
    config = StudyConfig()
    root = config.search_space.select_root()
    root.add_float_param("width", 64, 1024, scale_type=ScaleType.LOG)
    root.add_float_param("lr", 1e-3, 1e-1, scale_type=ScaleType.LOG)
    config.metrics.add("accuracy", "MAXIMIZE")
    config.metrics.add("latency_ms", "MINIMIZE")

    server = DefaultVizierServer()
    client = VizierClient.load_or_create_study(
        "pareto-demo", config, client_id="tuner", target=server.address)

    for _ in range(30):
        (trial,) = client.get_suggestions(count=1)
        client.complete_trial(evaluate(trial.parameters), trial_id=trial.id)

    frontier, vectors = client.pareto_frontier()
    print(f"Pareto frontier: {len(frontier)} of 30 trials "
          f"(hypervolume {client.hypervolume():.3f})")
    for trial, (acc, neg_lat) in sorted(zip(frontier, vectors),
                                        key=lambda p: -p[1][0]):
        # MINIMIZE metrics arrive sign-flipped (larger-is-better convention)
        print(f"  width={trial.parameters['width'].as_float:7.1f} "
              f"lr={trial.parameters['lr'].as_float:.4f} "
              f"accuracy={acc:.3f} latency_ms={-neg_lat:.2f}")
    client.close()
    server.stop()


if __name__ == "__main__":
    main()
