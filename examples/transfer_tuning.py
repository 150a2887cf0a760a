"""Transfer learning across studies (stacked residual GP).

A finished study's completed trials warm a NEW study on a related objective:
list the finished study in ``prior_studies`` and the GP-bandit fits one base
GP per prior study — each on the residuals of the stack so far — with the
current study's GP on top, so the very first suggestions already exploit the
prior landscape instead of sampling blind.

    PYTHONPATH=src python examples/transfer_tuning.py
"""

from repro import compile_cache
from repro.core import ScaleType, StudyConfig
from repro.service import DefaultVizierServer, VizierClient


def make_config() -> StudyConfig:
    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    root.add_float_param("lr", 1e-4, 1e-1, scale_type=ScaleType.LOG)
    root.add_float_param("momentum", 0.0, 1.0, scale_type=ScaleType.LINEAR)
    cfg.metrics.add("val_acc", "MAXIMIZE")
    cfg.algorithm = "GP_UCB"
    return cfg


def evaluate(params, *, lr_opt: float) -> float:
    """Toy objective family: a peaked response surface whose optimal learning
    rate differs between the prior task and the new task."""
    import math

    lr, mom = float(params["lr"]), float(params["momentum"])
    return -((math.log10(lr) - math.log10(lr_opt)) ** 2) - (mom - 0.9) ** 2


def main() -> None:
    compile_cache.enable()
    server = DefaultVizierServer()

    # 1. An earlier tuning run on a related task (e.g. the smaller model).
    prior = VizierClient.load_or_create_study(
        "resnet-small", make_config(), client_id="w0", target=server.address)
    for _ in range(20):
        (trial,) = prior.get_suggestions(count=1)
        prior.complete_trial(
            {"val_acc": evaluate(trial.parameters.as_dict(), lr_opt=3e-3)},
            trial_id=trial.id)

    # 2. The new study names the finished one in prior_studies; its trials
    #    ride the same wire frames the suggest already pays for.
    client = VizierClient.load_or_create_study(
        "resnet-large", make_config(), client_id="w0", target=server.address,
        prior_studies=[prior.study_name])
    best = float("-inf")
    for i in range(8):
        (trial,) = client.get_suggestions(count=1)
        acc = evaluate(trial.parameters.as_dict(), lr_opt=5e-3)  # shifted task
        client.complete_trial({"val_acc": acc}, trial_id=trial.id)
        best = max(best, acc)
        print(f"trial {i + 1}: val_acc={acc:+.4f}  best={best:+.4f}")

    for t in client.list_optimal_trials():
        print("optimal:", t.parameters.as_dict())
    prior.close()
    client.close()
    server.stop()


if __name__ == "__main__":
    main()
