#!/usr/bin/env python3
"""Chip smoke test: the served GP-bandit suggest path on one TPU chip.

    python3 chip_smoke.py [--seed N]

One process, one chip. It refuses to run on any backend but a TPU (there is
no CPU fallback), then runs three phases and prints one JSON line for each:

1. kernels: the Pallas Gram, fused Gram-matvec, triangular solve (both
   orientations) and rank-1 Cholesky update at the shapes a served
   suggest op gives them, each against a float64 NumPy oracle on the host;
   and a check that ``impl="auto"`` lowers to the Pallas kernels
   (``tpu_custom_call``) on this device.
2. posterior: a dense ``CholeskyPosterior`` at n=300 with fixed
   hyperparameters, its pool mean and std against a float64 NumPy GP.
3. served: ``DefaultVizierServer`` with two Pythia workers, driven by
   ``VizierClient`` over TCP on a 20-parameter mixed search space (30
   features) with a seeded rotated-ellipsoid objective: a dense
   single-objective study (n=300), a sparse one (n=5000, past
   ``SPARSE_THRESHOLD``) and a two-metric one (n=300), three suggest rounds
   each, every suggestion completed between rounds.

The ``seconds`` of each phase are set-up times that include compilation, not
metrics. The last line is ``{"ok": true, "device": {...}}``; any failure
raises, and the process exits non-zero without printing it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Largest error allowed against the float64 oracle, as max|err| / max|oracle|.
# Every value is f32 computed at HIGHEST matmul precision; bf16 passes would
# miss these by one to three orders of magnitude.
KERNEL_TOL = {"gram": 1e-4, "gram_matvec": 1e-4, "tri_solve": 1e-4,
              "tri_solve_trans": 1e-4, "cholupdate": 1e-4}
POSTERIOR_TOL = {"mean": 1e-3, "std": 1e-3}

# Served shapes: the policy's pool is 2000 Halton + 500 local candidates,
# padded to pool bucket 2560; a study of 300 trials pads to train bucket 512;
# the sparse path's inducing factor is 256 x 256.
TRAIN_BUCKET, POOL_BUCKET, N_INDUCING, DIM = 512, 2560, 256, 30


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def rel_err(got, want) -> "tuple[float, float]":
    err = float(np.max(np.abs(np.asarray(got, np.float64) - want)))
    return err, err / float(np.max(np.abs(want)))


def matern52_f64(x1, x2, amp):
    d2 = ((x1 * x1).sum(1)[:, None] - 2.0 * x1 @ x2.T + (x2 * x2).sum(1)[None])
    a = np.sqrt(5.0 * np.maximum(d2, 0.0))
    return amp * (1.0 + a + a * a / 3.0) * np.exp(-a)


def spd_factor(rng, m):
    a = rng.randn(m, m)
    return np.linalg.cholesky(a @ a.T / m + np.eye(m))


def check_errors(phase: str, errors: dict, tol: dict) -> None:
    bad = {k: errors[k]["rel"] for k in tol if not errors[k]["rel"] <= tol[k]}
    if bad:
        raise AssertionError(f"{phase}: beyond tolerance {tol}: {bad}")


# ---------------------------------------------------------------------------
# Phase 1: kernels at served shapes
# ---------------------------------------------------------------------------


def phase_kernels(seed: int, *, n=TRAIN_BUCKET, m_pool=POOL_BUCKET,
                  m=N_INDUCING, d=DIM) -> dict:
    import jax.numpy as jnp
    from repro.kernels import ops

    rng = np.random.RandomState(seed)
    ell = 2.0 * np.exp(rng.uniform(-0.3, 0.3, d))
    x1 = rng.rand(n, d) / ell
    x2 = rng.rand(m_pool, d) / ell
    alpha = rng.randn(n)
    amp = 1.3
    L = spd_factor(rng, m)
    rhs = rng.randn(m, m_pool)
    v = rng.randn(m)
    f32 = lambda a: jnp.asarray(np.asarray(a, np.float32))
    got = {
        "gram": ops.matern52_gram(f32(x1), f32(x2), amp),
        "gram_matvec": ops.matern52_gram_matvec(f32(x1), f32(x2), f32(alpha),
                                                amp),
        "tri_solve": ops.tri_solve(f32(L), f32(rhs)),
        "tri_solve_trans": ops.tri_solve(f32(L), f32(rhs), trans=True),
        "cholupdate": ops.cholupdate(f32(L), f32(v)),
    }
    # the oracles see the same f32-rounded inputs the device saw
    x1, x2, alpha, L, rhs, v = (np.asarray(a, np.float32).astype(np.float64)
                                for a in (x1, x2, alpha, L, rhs, v))
    K = matern52_f64(x1, x2, amp)
    want = {
        "gram": K,
        "gram_matvec": K.T @ alpha,
        "tri_solve": np.linalg.solve(L, rhs),
        "tri_solve_trans": np.linalg.solve(L.T, rhs),
        "cholupdate": np.linalg.cholesky(L @ L.T + np.outer(v, v)),
    }
    errors = {}
    for k in got:
        err, rel = rel_err(got[k], want[k])
        errors[k] = {"max_abs": err, "rel": rel}
    check_errors("kernels", errors, KERNEL_TOL)
    return {"shapes": {"gram": [n, m_pool, d], "tri_solve": [m, m_pool],
                       "cholupdate": [m]},
            "errors": errors, "tolerance_rel": KERNEL_TOL}


def check_kernel_dispatch(*, n=TRAIN_BUCKET, m_pool=POOL_BUCKET, m=N_INDUCING,
                          d=DIM) -> dict:
    """``impl="auto"`` must reach the Pallas kernels on this device."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    progs = {
        "gram": (lambda a, b: ops.matern52_gram(a, b, 1.0), S(n, d), S(m_pool, d)),
        "tri_solve": (lambda L, b: ops.tri_solve(L, b), S(m, m), S(m, m_pool)),
        "cholupdate": (lambda L, v: ops.cholupdate(L, v), S(m, m), S(m)),
    }
    found = {k: "tpu_custom_call" in jax.jit(f).lower(*args).as_text()
             for k, (f, *args) in progs.items()}
    if not all(found.values()):
        raise AssertionError(f"impl='auto' did not reach Pallas: {found}")
    return {"tpu_custom_call": found}


# ---------------------------------------------------------------------------
# Phase 2: dense posterior against a float64 GP
# ---------------------------------------------------------------------------


def phase_posterior(seed: int, *, n=300, m_pool=2500, d=DIM) -> dict:
    from repro.pythia.posterior import _JITTER, CholeskyPosterior

    rng = np.random.RandomState(seed + 1)
    ell = 2.0 * np.exp(rng.uniform(-0.3, 0.3, d))
    amp, noise = 1.3, 1e-2
    raw = {"log_amp": np.float32(np.log(amp)),
           "log_ell": np.log(ell).astype(np.float32),
           "log_noise": np.float32(np.log(noise))}
    x = rng.rand(n, d).astype(np.float32)
    y = np.sin(3.0 * x).sum(1)
    y = ((y - y.mean()) / y.std()).astype(np.float32)
    xq = rng.rand(m_pool, d).astype(np.float32)
    post = CholeskyPosterior(raw, x, y, capacity=n)
    post.set_pool(xq)
    mean, std = post.pool_mean(), post.pool_std()

    ell64 = np.exp(raw["log_ell"].astype(np.float64))
    amp64 = float(np.exp(np.float64(raw["log_amp"])))
    noise64 = float(np.exp(np.float64(raw["log_noise"]))) + _JITTER
    xs, xqs = x.astype(np.float64) / ell64, xq.astype(np.float64) / ell64
    Lk = np.linalg.cholesky(matern52_f64(xs, xs, amp64) + noise64 * np.eye(n))
    Kq = matern52_f64(xs, xqs, amp64)
    V = np.linalg.solve(Lk, Kq)
    w = np.linalg.solve(Lk, y.astype(np.float64))
    want_mean = V.T @ w
    want_std = np.sqrt(np.maximum(amp64 - (V * V).sum(0), 1e-10))
    errors = {}
    for k, got, want in (("mean", mean, want_mean), ("std", std, want_std)):
        err, rel = rel_err(got, want)
        errors[k] = {"max_abs": err, "rel": rel}
    check_errors("posterior", errors, POSTERIOR_TOL)
    return {"n": n, "pool": m_pool, "dim": d, "errors": errors,
            "tolerance_rel": POSTERIOR_TOL}


# ---------------------------------------------------------------------------
# Phase 3: the served suggest path
# ---------------------------------------------------------------------------


def make_config(n_metrics: int):
    from repro.core import ScaleType, StudyConfig

    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    for i in range(12):
        if i < 4:
            root.add_float_param(f"f{i:02d}", 1e-4, 1.0, scale_type=ScaleType.LOG)
        else:
            root.add_float_param(f"f{i:02d}", -5.0, 5.0)
    for i in range(4):
        root.add_int_param(f"i{i}", 0, 10)
    for i, k in enumerate((3, 3, 4, 4)):
        root.add_categorical_param(f"c{i}", [f"v{j}" for j in range(k)])
    for j in range(n_metrics):
        cfg.metrics.add(f"obj{j}", goal="MINIMIZE")
    cfg.algorithm = "DEFAULT"
    return cfg


class Objective:
    """Seeded BBOB-style objectives over the 20 parameters, each mapped to
    [0, 1] by its own scale (independent of the code under test): metric 0
    is a rotated ellipsoid (condition 1e6), metric 1 a shifted sphere."""

    def __init__(self, seed: int, config):
        rng = np.random.RandomState(seed + 2)
        self._params = list(config.search_space.parameters)
        d = len(self._params)
        self._rot, _ = np.linalg.qr(rng.randn(d, d))
        self._opt = rng.uniform(0.2, 0.8, (2, d))
        self._scale = 10.0 ** (6.0 * np.arange(d) / (d - 1))

    def _unit(self, params) -> np.ndarray:
        from repro.core import ScaleType

        u = []
        for cfg in self._params:
            pv = params[cfg.name]
            if cfg.categories:
                u.append(cfg.categories.index(pv.as_str) / (len(cfg.categories) - 1))
                continue
            lo, hi = cfg.bounds
            val = pv.as_float
            if cfg.scale_type == ScaleType.LOG:
                u.append(math.log(val / lo) / math.log(hi / lo))
            else:
                u.append((val - lo) / (hi - lo))
        return np.asarray(u)

    def __call__(self, params, n_metrics: int) -> dict:
        u = self._unit(params)
        z = self._rot @ (u - self._opt[0])
        values = [float(self._scale @ (z * z)),
                  float(np.sum((u - self._opt[1]) ** 2))]
        return {f"obj{j}": values[j] for j in range(n_metrics)}


def _seed_study(client, config, objective, n, n_metrics, seed):
    from repro.core import Measurement, Trial

    rng = random.Random(seed)
    for i in range(n):
        params = config.search_space.sample(rng)
        t = Trial(id=i + 1, parameters=params)
        t.complete(Measurement(metrics=objective(params, n_metrics)))
        client.add_trial(t)


def _check_suggestions(trials, config, count, study) -> None:
    if len(trials) != count:
        raise AssertionError(f"{study}: {len(trials)} suggestions, wanted {count}")
    keys = set()
    for t in trials:
        for cfg in config.search_space.parameters:
            if cfg.name not in t.parameters or not cfg.contains(t.parameters[cfg.name]):
                raise AssertionError(f"{study}: trial {t.id} out of domain at {cfg.name}")
        keys.add(tuple(sorted((k, v.value) for k, v in t.parameters.items())))
    if len(keys) != count:
        raise AssertionError(f"{study}: {count - len(keys)} duplicate suggestions")


def _fit_state(client):
    from repro.core.metadata import Namespace
    from repro.pythia.state import GP_BANDIT_NAMESPACE, STATE_KEY, PolicyState

    md = client.get_study_metadata()
    return PolicyState.from_value(
        md.abs_ns(Namespace(GP_BANDIT_NAMESPACE)).get(STATE_KEY))


def run_study(server, name, *, n, count, n_metrics, seed, rounds=3) -> dict:
    from repro.pythia.sparse_posterior import SPARSE_THRESHOLD
    from repro.service import VizierClient

    config = make_config(n_metrics)
    objective = Objective(seed, config)
    client = VizierClient.load_or_create_study(
        name, config, client_id="smoke", target=server.address)
    t0 = time.perf_counter()
    _seed_study(client, config, objective, n, n_metrics, seed)
    seed_seconds = time.perf_counter() - t0
    completed = n
    op_seconds = []
    for r in range(rounds):
        t0 = time.perf_counter()
        trials = client.get_suggestions(count=count)
        op_seconds.append(time.perf_counter() - t0)
        _check_suggestions(trials, config, count, name)
        state = _fit_state(client)
        if state.num_trials != completed or state.dim != DIM:
            raise AssertionError(
                f"{name}: fit state (trials {state.num_trials}, dim "
                f"{state.dim}), wanted ({completed}, {DIM})")
        if state.steps_run < 1 or state.adam_t < 1:
            raise AssertionError(f"{name}: the GP fit did not run or diverged")
        if r > 0 and not state.warm_started:
            raise AssertionError(f"{name}: round {r} did not warm-start")
        if len(state.metric_states) != (n_metrics if n_metrics > 1 else 0):
            raise AssertionError(f"{name}: {len(state.metric_states)} metric fits")
        for t in trials:
            client.complete_trial(objective(t.parameters, n_metrics), trial_id=t.id)
        completed += count
    client.close()
    return {"study": name, "n_seeded": n, "count": count, "metrics": n_metrics,
            "rounds": rounds, "sparse": n > SPARSE_THRESHOLD,
            "fit_num_trials": state.num_trials,
            "fit_steps_last_round": state.steps_run,
            "seed_seconds": seed_seconds,
            "op_seconds_incl_compile": op_seconds}


def phase_served(seed: int, *, n_dense=300, n_sparse=5000) -> dict:
    from repro.pythia.sparse_posterior import SPARSE_THRESHOLD
    from repro.service import DefaultVizierServer

    if n_sparse <= SPARSE_THRESHOLD:
        raise ValueError("the sparse study must exceed SPARSE_THRESHOLD")
    # a cold op compiles a few dozen programs: give the lease room for that
    server = DefaultVizierServer(n_pythia_workers=2, lease_timeout=600.0)
    try:
        studies = [
            run_study(server, "dense", n=n_dense, count=8, n_metrics=1, seed=seed),
            run_study(server, "sparse", n=n_sparse, count=8, n_metrics=1,
                      seed=seed + 10),
            run_study(server, "two_metric", n=n_dense, count=4, n_metrics=2,
                      seed=seed + 20),
        ]
    finally:
        server.stop()
    return {"studies": studies}


# ---------------------------------------------------------------------------


def run_phase(name: str, fn, *args, **kwargs) -> None:
    t0 = time.perf_counter()
    record = {"phase": name, **fn(*args, **kwargs)}
    record["seconds_setup_incl_compile"] = time.perf_counter() - t0
    emit(record)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices)",
              file=sys.stderr)
        return 2

    from repro import compile_cache

    compile_cache.enable()
    t0 = time.perf_counter()
    run_phase("kernels", phase_kernels, args.seed)
    run_phase("dispatch", check_kernel_dispatch)
    run_phase("posterior", phase_posterior, args.seed)
    run_phase("served", phase_served, args.seed)
    emit({"phase": "total", "seconds_setup_incl_compile": time.perf_counter() - t0})
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
