"""Paper §5: convergence vs #workers, with injected worker crashes.

Synthetic objective (6-d shifted sphere in log space); measures best-so-far
after a fixed trial budget for 1 vs 4 workers, and with a crash+rebind in
the middle (result must not regress — the reassigned trial completes).
"""

import threading
import time

from benchmarks.bench_util import emit

from repro import compile_cache
from repro.core import ScaleType, StudyConfig
from repro.service import DefaultVizierServer, VizierClient


def objective(params) -> float:
    import math

    total = 0.0
    for i in range(6):
        x = params.get_value(f"x{i}")
        total -= (x - 0.3 - 0.05 * i) ** 2
    return total


def _config() -> StudyConfig:
    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    for i in range(6):
        root.add_float_param(f"x{i}", 0.0, 1.0, scale_type=ScaleType.LINEAR)
    cfg.metrics.add("obj", "MAXIMIZE")
    cfg.algorithm = "GP_UCB"
    return cfg


def run_study(n_workers: int, budget: int, crash: bool = False) -> float:
    server = DefaultVizierServer(reassign_stalled_after=0.5)
    seed = VizierClient.load_or_create_study(
        f"pt-{n_workers}-{crash}", _config(), client_id="seed",
        target=server.address)
    done = {"count": 0}
    lock = threading.Lock()

    def worker(wid, max_trials):
        from repro.service.rpc import StatusCode, VizierRpcError

        c = VizierClient(server.address, seed.study_name, f"w{wid}")
        while True:
            with lock:
                if done["count"] >= budget:
                    return
            (t,) = c.get_suggestions(count=1)
            try:
                c.complete_trial({"obj": objective(t.parameters)}, trial_id=t.id)
            except VizierRpcError as e:
                # a reassigned trial may race to completion between workers —
                # the service correctly rejects the second CompleteTrial
                if e.code != StatusCode.FAILED_PRECONDITION:
                    raise
            with lock:
                done["count"] += 1

    if crash:
        # worker 0 takes a trial and dies; its trial must be recovered
        c0 = VizierClient(server.address, seed.study_name, "w0")
        c0.get_suggestions(count=1)
        time.sleep(0.6)  # exceed the stall timeout

    threads = [threading.Thread(target=worker, args=(i, budget))
               for i in range(n_workers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    trials = seed.list_trials()
    best = max(t.final_objective("obj") for t in trials
               if t.final_objective("obj") is not None)
    server.stop()
    return best, wall, len(trials)


def main() -> None:
    compile_cache.enable()
    for workers, crash in [(1, False), (4, False), (4, True)]:
        best, wall, n = run_study(workers, budget=24, crash=crash)
        emit(f"sec5.parallel.workers={workers}.crash={crash}",
             wall / max(n, 1) * 1e6,
             f"best={best:.4f} trials={n} wall_s={wall:.1f}")


if __name__ == "__main__":
    main()
