"""Paper Table 1: the feature matrix, exercised end-to-end.

Each claimed feature (multi-objective, early stopping, transfer learning,
conditional search, parallel trials, any-language client = wire protocol)
runs for real; the benchmark reports per-feature latency and PASS/FAIL.
"""

from benchmarks.bench_util import emit, timeit

from repro import compile_cache
from repro.core import (
    AutomatedStoppingConfig,
    Measurement,
    ScaleType,
    StudyConfig,
    Trial,
    TrialState,
)
from repro.service import DefaultVizierServer, VizierClient


def _base_config(algorithm="RANDOM_SEARCH") -> StudyConfig:
    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    root.add_float_param("x", 0.0, 1.0, scale_type=ScaleType.LINEAR)
    cfg.metrics.add("obj", "MAXIMIZE")
    cfg.algorithm = algorithm
    return cfg


def bench_multi_objective(server) -> str:
    cfg = _base_config()
    cfg.metrics.add("cost", "MINIMIZE")
    c = VizierClient.load_or_create_study("f-mo", cfg, client_id="c",
                                          target=server.address)
    for i in range(6):
        (t,) = c.get_suggestions(count=1)
        x = t.parameters.get_value("x")
        c.complete_trial({"obj": x, "cost": x * x}, trial_id=t.id)
    front = c.list_optimal_trials()
    assert 1 <= len(front) <= 6
    return f"pareto_front={len(front)}"


def bench_early_stopping(server) -> str:
    cfg = _base_config()
    cfg.automated_stopping = (
        AutomatedStoppingConfig.median_automated_stopping_config(
            min_completed_trials=1))
    c = VizierClient.load_or_create_study("f-es", cfg, client_id="c",
                                          target=server.address)
    (good,) = c.get_suggestions(count=1)
    for s, v in [(1, 0.8), (2, 0.9)]:
        c.report_intermediate_objective_value({"obj": v}, trial_id=good.id, step=s)
    c.complete_trial({"obj": 0.9}, trial_id=good.id)
    (bad,) = c.get_suggestions(count=1)
    c.report_intermediate_objective_value({"obj": 0.05}, trial_id=bad.id, step=1)
    c.report_intermediate_objective_value({"obj": 0.06}, trial_id=bad.id, step=2)
    assert c.should_trial_stop(bad.id) is True
    return "median_rule_stops=True"


def bench_transfer_learning(server) -> str:
    cfg = _base_config()
    c = VizierClient.load_or_create_study("f-tl", cfg, client_id="c",
                                          target=server.address)
    prior = Trial(parameters={"x": 0.7})
    prior.complete(Measurement(metrics={"obj": 0.99}))
    added = c.add_trial(prior)  # seed from a prior study
    assert c.get_trial(added.id).state == TrialState.COMPLETED
    return "prior_trials_injected=1"


def bench_conditional_search(server) -> str:
    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    model = root.add_categorical_param("model", ["linear", "dnn"])
    model.select_values(["dnn"]).add_int_param("layers", 1, 4)
    cfg.metrics.add("obj", "MAXIMIZE")
    cfg.algorithm = "RANDOM_SEARCH"
    c = VizierClient.load_or_create_study("f-cond", cfg, client_id="c",
                                          target=server.address)
    kinds = set()
    for _ in range(8):
        (t,) = c.get_suggestions(count=1)
        has_layers = "layers" in t.parameters
        assert has_layers == (t.parameters.get_value("model") == "dnn")
        kinds.add(t.parameters.get_value("model"))
        c.complete_trial({"obj": 0.5}, trial_id=t.id)
    return f"models_seen={len(kinds)}"


def bench_parallel_trials(server) -> str:
    cfg = _base_config()
    c = VizierClient.load_or_create_study("f-par", cfg, client_id="seed",
                                          target=server.address)
    clients = [VizierClient(server.address, c.study_name, f"w{i}")
               for i in range(4)]
    trials = [cl.get_suggestions(count=1)[0] for cl in clients]
    assert len({t.id for t in trials}) == 4
    for cl, t in zip(clients, trials):
        cl.complete_trial({"obj": 0.1}, trial_id=t.id)
    return "parallel_clients=4"


def main() -> None:
    compile_cache.enable()
    server = DefaultVizierServer()
    for name, fn in [
        ("table1.multi_objective", bench_multi_objective),
        ("table1.early_stopping", bench_early_stopping),
        ("table1.transfer_learning", bench_transfer_learning),
        ("table1.conditional_search", bench_conditional_search),
        ("table1.parallel_trials", bench_parallel_trials),
    ]:
        import time

        t0 = time.perf_counter()
        derived = fn(server)
        us = (time.perf_counter() - t0) * 1e6
        emit(name, us, f"PASS {derived}")
    server.stop()


if __name__ == "__main__":
    main()
