#!/usr/bin/env python3
"""Runs one benchmark cell once, on the TPU of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (a fleet of studies, its
server and datastore) and a traffic mix (an open-loop stream of worker
report-and-ask events). One run:

1. starts a ``DefaultVizierServer`` in this process, which holds the chip,
   with the configuration's workers, shards and sharded SQLite store (a
   fresh directory under ``$TMPDIR``) and the compile cache at
   ``<checkout>/.jax_cache``;
2. seeds the studies straight through the datastore, in one transaction
   per study;
3. warms up: one suggest op per study (its fit state is then persisted),
   and a second one on one study of each train bucket while the first one's
   trials are pending, so every program the window runs is compiled or
   loaded from the cache; the trials are then completed;
4. drives the cell's events for ``--seconds`` (``--trace 1`` records a
   profiler trace of the window's first ``TRACE_S`` seconds);
5. compares what the window produced with the plain reference
   (``bench/lib/check.py``) and checks the service's guarantees;
6. prints the result as the last line of standard output, and each number
   compared beside its limit as the last lines of standard error.

It exits with 2, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from concurrent import futures  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.lib import capture, cells, check  # noqa: E402
from bench.lib import plan as plan_lib  # noqa: E402
from bench.lib.context import RunContext  # noqa: E402
from bench.lib.objectives import StudyObjective, build_space  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
GRACE_S = 60.0
SAMPLE_CALLS = 12
# The profiler records the window's first TRACE_S seconds: reading a trace
# of the whole window would take minutes of the run's time limit.
TRACE_S = 10.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_info(chips: int, require_tpu: bool = True):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def seed_studies(datastore, plan, configs, objectives, seed: int):
    """Creates the studies and their completed trials through the datastore."""
    from repro.core import Measurement, Study, Trial

    names = []
    for s, (size, cfg, obj) in enumerate(zip(plan.study_sizes, configs,
                                             objectives)):
        name = f"owners/bench/studies/s{s:02d}"
        datastore.create_study(Study(name=name, display_name=f"s{s:02d}",
                                     study_config=cfg))
        rng = random.Random(seed * 1_000_003 + s)
        with datastore.study_transaction(name):
            for i in range(size):
                params = cfg.search_space.sample(rng)
                t = Trial(id=i + 1, parameters=params)
                t.complete(Measurement(metrics=obj(params)))
                datastore.create_trial(name, t)
        names.append(name)
    return names


def warm_up(load, plan) -> None:
    """One ask per study; a second ask, while the first one's trials are
    pending, on one study of each bucket; then every trial is reported."""
    n = len(plan.study_sizes)
    first = {}
    for s, b in enumerate(plan.study_buckets):
        first.setdefault(b, s)
    now = time.perf_counter
    with futures.ThreadPoolExecutor(16) as ex:
        list(ex.map(lambda s: load.run_event(-1, s, 0, plan.warm_count,
                                               now()), range(n)))
        if plan.workers > 1:
            list(ex.map(lambda s: load.run_event(-1, s, 1, plan.warm_count,
                                                   now()), first.values()))
    load.complete_held()
    bad = [r for r in load.records if not r.ok]
    if bad:
        raise RuntimeError(f"warm-up op failed: {bad[0].error}")
    load.records.clear()


def warm_fit_programs(plan, dim: int) -> None:
    """Compiles the fit's closing loss evaluation (``_mll_grad``) at each
    bucket a fit will use. A fit runs it only when Adam ends unconverged,
    which the warm-up ops cannot be counted on to do, so it would otherwise
    compile inside the window on the first such fit. A program without it
    has nothing to warm here."""
    import jax.numpy as jnp

    from repro.pythia import gp_bandit, posterior, sparse_posterior

    mll_grad = getattr(gp_bandit, "_mll_grad", None)
    if mll_grad is None:
        return
    sub = getattr(gp_bandit, "FIT_SUBSAMPLE", None)
    buckets = set()
    for n in plan.study_sizes:
        rows = (sub if sub and n > sparse_posterior.SPARSE_THRESHOLD else n)
        buckets.add(posterior.train_bucket(rows))
    raw = {"log_amp": jnp.zeros((), jnp.float32),
           "log_ell": jnp.full((dim,), jnp.log(0.3), jnp.float32),
           "log_noise": jnp.asarray(jnp.log(1e-2), jnp.float32)}
    for b in sorted(buckets):
        mask = jnp.zeros((b,), jnp.float32)
        loss, _ = mll_grad(raw, jnp.zeros((b, dim), jnp.float32), mask, mask)
        loss.block_until_ready()


class CompileCounter:
    """Counts traces and compiles JAX reports while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_a, **_k):
        if self.on and name in self.EVENTS:
            self.count += 1


def run_cell(cell, seed: int, seconds: float, trace: bool,
             device: dict, *, sample_calls: int = SAMPLE_CALLS,
             grace_s: float = GRACE_S, fault=None, controls=(),
             contexts: Optional[list] = None) -> dict:
    """Everything after the device check. ``fault`` (tests only) is called
    with the server once it is up, to break the timed path underneath.
    ``controls`` (``bench/tools/limits.py`` only) names lower precisions at
    which the reference is also put in the program's place; their numbers
    come back under ``controls``. ``contexts``, when given, receives the
    run's ``RunContext`` (the knee sweep reads it)."""
    import jax

    from repro import compile_cache
    from repro.pythia import posterior as post_lib
    from repro.service import DefaultVizierServer

    compile_cache.enable()
    capture.install()
    config, traffic = cell.config, cell.traffic
    plan = plan_lib.build(config, traffic, seed, seconds)
    n = len(plan.study_sizes)
    configs = [build_space(config["search_space"]) for _ in range(n)]
    objectives = [StudyObjective(plan.objectives[s], configs[s],
                                 seed * 7919 + s) for s in range(n)]
    srv = config["server"]
    tmp = tempfile.mkdtemp(prefix="bench-")
    db_dir = os.path.join(tmp, "store")
    server = DefaultVizierServer(
        database_path=db_dir, database_shards=int(srv["database_shards"]),
        database_synchronous=srv["database_synchronous"],
        n_pythia_workers=int(srv["n_pythia_workers"]),
        n_shards=int(srv["n_shards"]), lease_timeout=float(srv["lease_timeout"]))
    capture.install_datastore_reads(type(server.datastore))
    load = None
    try:
        names = seed_studies(server.datastore, plan, configs, objectives, seed)
        t_seeded = time.perf_counter()
        from bench.lib.loadgen import LoadGen

        load = LoadGen(server.address, names, configs, objectives,
                       plan.workers)
        warm_up(load, plan)
        from repro.pythia.converters import TrialToArrayConverter

        warm_fit_programs(plan, TrialToArrayConverter(configs[0].search_space).dim)
        t_warm = time.perf_counter()
        log(f"seeded {sum(plan.study_sizes)} trials in "
            f"{t_seeded - T_PROC:.3f} s from start; warm-up "
            f"{t_warm - t_seeded:.3f} s")
        if fault is not None:
            fault(server)

        counter = CompileCounter()
        traces_before = dict(post_lib.TRACE_COUNTS)
        recorder = capture.Recorder(trace=trace)
        trace_dir = os.path.join(tmp, "trace")
        capture.start(recorder)
        counter.on = True
        span = []
        stopper = None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

            def stop_trace():
                span.append(time.perf_counter())
                jax.profiler.stop_trace()
            stopper = threading.Timer(0.2 + min(TRACE_S, seconds), stop_trace)
            stopper.start()
        span.append(time.perf_counter())
        t0 = span[0] + 0.2
        close = load.run(plan.events, t0, grace_s)
        if stopper is not None:
            stopper.join()
        counter.on = False
        capture.stop()
        retraced = {k: v - traces_before.get(k, 0)
                    for k, v in post_lib.TRACE_COUNTS.items()
                    if v != traces_before.get(k, 0)}
        window_compiles = counter.count + sum(retraced.values())
        memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices())
        gen, load = load, None
        gen.close()
        server.stop()
        server = None

        ctx = RunContext(cell=cell, plan=plan, seconds=seconds, t_proc=T_PROC,
                         t0=t0, close=close, grace_s=grace_s,
                         records=list(gen.records),
                         unfinished_due=list(gen.unfinished_due),
                         late_s=list(gen.late_s), recorder=recorder,
                         window_compiles=window_compiles)
        if contexts is not None:
            contexts.append(ctx)
        late = sorted(gen.late_s) or [0.0]
        print(json.dumps({"generator_late_ms": {
            "p50": late[len(late) // 2] * 1e3, "max": late[-1] * 1e3,
            "events": len(gen.late_s)}, "window_compiles": window_compiles,
            "engine_retraces": retraced,
            "policy_calls": len(recorder.calls)}), flush=True)

        if trace:
            from bench import trace_reduce

            ctx.peaks = cells.peaks(device["kind"])
            ctx.trace = trace_reduce.reduce_dir(trace_dir,
                                                window_s=span[1] - span[0])
            ctx.trace_span = (span[0], span[1])
        metrics = {}
        for spec in (cell.per_layer if trace else cell.end_to_end):
            value = cells.metric_reader(spec["name"]).read(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}

        # the correctness check, after the window and the memory reading
        calls = check.sample_calls(recorder.calls, seed, sample_calls)
        check.fetch(calls)
        recorder.calls = []
        numbers = check.policy_numbers(calls, config)
        control_numbers = {p: check.policy_numbers(calls, config, control=p)
                           for p in controls}
        numbers.update(check.service_numbers(
            ctx.records, len(ctx.unfinished_due), names, configs,
            check.read_back(db_dir)))
        limits = config["limits"]
        for k in sorted(set(numbers) - set(limits)):
            log(f"not compared in this configuration: {k} = {numbers[k]!r}")
        checks = {k: {"value": numbers[k], "limit": limits[k]}
                  for k in numbers if k in limits}
        correct = bool(calls) and all(
            v["value"] <= v["limit"] for v in checks.values())
        ops = ctx.records
        result = {
            "correct": correct,
            "attempted": len(ops) + len(ctx.unfinished_due),
            "failed": sum(1 for r in ops if not r.ok) + len(ctx.unfinished_due),
            "metrics": metrics,
            "device": dict(device, memory_peak_bytes=int(memory_peak)),
        }
        if trace:
            result["device"].update(busy_s=ctx.trace.busy_s,
                                    window_s=ctx.trace.window_s)
            result["breakdown"] = ctx.trace.breakdown()
        if controls:
            result["controls"] = control_numbers
        result["checks"] = checks
        log(f"compared {len(calls)} policy calls of {sample_calls} sampled")
        return result
    finally:
        if load is not None:
            load.close()
        if server is not None:
            server.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    cell = cells.load_cell(args.workload)
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    try:
        device = device_info(cell.chips)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2
    log(f"device {json.dumps(device)}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
