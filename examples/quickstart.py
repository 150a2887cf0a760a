"""Quickstart (paper Code Block 1): tune a blackbox function via the service.

    PYTHONPATH=src python examples/quickstart.py
"""

import math
import sys

sys.path.insert(0, "src")

from repro import compile_cache
from repro.core import ScaleType, StudyConfig
from repro.service import DefaultVizierServer, VizierClient


def evaluate_trial(params) -> float:
    """Branin-ish objective over (lr, layers) — maximize."""
    lr = params["lr"].as_float
    layers = params["layers"].as_int
    return -(math.log10(lr) + 2.5) ** 2 - 0.1 * (layers - 3) ** 2


def main():
    compile_cache.enable()
    server = DefaultVizierServer(host="127.0.0.1")

    config = StudyConfig()
    root = config.search_space.select_root()
    root.add_float_param("lr", 1e-4, 1e-1, scale_type=ScaleType.LOG)
    root.add_int_param("layers", 1, 6)
    config.metrics.add("objective", goal="MAXIMIZE")
    config.algorithm = "GP_UCB"

    client = VizierClient.load_or_create_study(
        "quickstart", config, client_id="worker_0", target=server.address)

    for _ in range(15):
        suggestions = client.get_suggestions(count=1)
        if not suggestions:
            break
        for trial in suggestions:
            value = evaluate_trial(trial.parameters)
            client.complete_trial({"objective": value}, trial_id=trial.id)
            print(f"trial {trial.id}: lr={trial.parameters['lr'].as_float:.5f} "
                  f"layers={trial.parameters['layers'].as_int} -> {value:.4f}")

    best = client.list_optimal_trials()[0]
    print(f"\nbest: {best.parameters.as_dict()} -> "
          f"{best.final_objective('objective'):.4f} (optimum ~ 0 at lr=3.16e-3, layers=3)")
    server.stop()


if __name__ == "__main__":
    main()
