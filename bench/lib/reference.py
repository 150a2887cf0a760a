"""The plain reference: a dense GP and a Titsias SGPR in ``jax.numpy``.

It imports nothing of the program. It takes the data a suggest op was
given (the design X and labels y, the pending trials' features and
fantasized values, the candidate pool) and the hyperparameters the op
served, and works out from them, in float32 with every contraction at the
``precision`` it is given ("highest" for the reference; the control passes
a lower one):

* the fit's objective: the negative log marginal likelihood of a Matern-5/2
  ARD GP with noise exp(log_noise) + 1e-4, plus the fit's weak log-normal
  priors (log_amp ~ N(0, 1), log_ell ~ N(log 0.3, 1), log_noise ~
  N(log 1e-2, 2^2)), the objective the program's Adam fit minimizes;
* the fit's first Adam steps (three at most) from the state the program's
  fit started from (hyperparameters and moments) with its schedule (bias
  corrections and learning rate): the gradient of that objective and
  Adam's update with the fit's clamps;
* the acquisition the op scored: for each batch member b in turn, the UCB
  mean + 1.8 std over the whole pool, with the pending trials and the
  members before b conditioned on (a member at the reference's own
  posterior mean), the member the program picked taken as given.

Dense: one Cholesky of K(X, X) + noise I per member. Sparse: the SGPR
posterior of Titsias (2009) in the GPflow form, A = Luu^-1 Kuf / sigma,
B = I + A A^T, with m = 256 inducing points at the scrambled-Halton sites
the configuration's policy seed gives (``halton.py`` here, a copy of the
program's generator), recomputed from the whole design for each member.
Rows are zero-padded to fixed sizes with an identity block, which leaves
every result exact and lets each padded shape compile once.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

JITTER = 1e-4
BETA = 1.8
PAD_ROWS = 256


def _pad_to(n: int, step: int = PAD_ROWS) -> int:
    return max(step, -(-n // step) * step)


def _gram(x1, x2, amp, precision):
    cross = jnp.dot(x1, x2.T, precision=precision)
    d2 = jnp.maximum(jnp.sum(x1 * x1, 1)[:, None] - 2.0 * cross
                     + jnp.sum(x2 * x2, 1)[None, :], 0.0)
    a = jnp.sqrt(5.0 * d2)
    return amp * (1.0 + a + a * a / 3.0) * jnp.exp(-a)


def _solve(L, b, precision):
    with jax.default_matmul_precision(precision):
        return jax.scipy.linalg.solve_triangular(L, b, lower=True)


@functools.partial(jax.jit, static_argnames=("precision",))
def neg_mll(raw: Dict, x, y, mask, *, precision: str):
    amp = jnp.exp(raw["log_amp"])
    ell = jnp.exp(raw["log_ell"])
    noise = jnp.exp(raw["log_noise"]) + JITTER
    xs = x / ell
    K = _gram(xs, xs, amp, precision) * (mask[:, None] * mask[None, :])
    K = K + jnp.diag(noise * mask + (1.0 - mask))
    with jax.default_matmul_precision(precision):
        L = jnp.linalg.cholesky(K)
    w = _solve(L, y * mask, precision)
    mll = (-0.5 * jnp.dot(w, w, precision=precision)
           - jnp.sum(jnp.log(jnp.diagonal(L)))
           - 0.5 * jnp.sum(mask) * jnp.log(2.0 * jnp.pi))
    prior = (-0.5 * raw["log_amp"] ** 2
             - 0.5 * jnp.sum((raw["log_ell"] - jnp.log(0.3)) ** 2)
             - 0.5 * (raw["log_noise"] - jnp.log(1e-2)) ** 2 / 4.0)
    return -(mll + prior)


@functools.partial(jax.jit, static_argnames=("precision",))
def dense_pool(raw: Dict, x, y, mask, q, *, precision: str):
    """(mean, std) over the pool q of the GP conditioned on the masked rows."""
    amp = jnp.exp(raw["log_amp"])
    ell = jnp.exp(raw["log_ell"])
    noise = jnp.exp(raw["log_noise"]) + JITTER
    xs, qs = x / ell, q / ell
    K = _gram(xs, xs, amp, precision) * (mask[:, None] * mask[None, :])
    K = K + jnp.diag(noise * mask + (1.0 - mask))
    with jax.default_matmul_precision(precision):
        L = jnp.linalg.cholesky(K)
    V = _solve(L, _gram(xs, qs, amp, precision) * mask[:, None], precision)
    w = _solve(L, y * mask, precision)
    mean = jnp.dot(V.T, w, precision=precision)
    var = amp - jnp.sum(V * V, axis=0)
    return mean, jnp.sqrt(jnp.maximum(var, 1e-10))


@functools.partial(jax.jit, static_argnames=("precision",))
def sparse_pool(raw: Dict, z, x, y, mask, q, *, precision: str):
    """(mean, std) over the pool q of the SGPR on the masked rows."""
    amp = jnp.exp(raw["log_amp"])
    ell = jnp.exp(raw["log_ell"])
    sigma = jnp.sqrt(jnp.exp(raw["log_noise"]) + JITTER)
    zs, xs, qs = z / ell, x / ell, q / ell
    m = z.shape[0]
    Kuu = _gram(zs, zs, amp, precision) + JITTER * jnp.eye(m)
    with jax.default_matmul_precision(precision):
        Luu = jnp.linalg.cholesky(Kuu)
        A = _solve(Luu, _gram(zs, xs, amp, precision) * mask[None, :],
                   precision) / sigma
        B = jnp.eye(m) + jnp.dot(A, A.T, precision=precision)
        LB = jnp.linalg.cholesky(B)
        c = _solve(LB, jnp.dot(A, y * mask, precision=precision),
                   precision) / sigma
        Qu = _solve(Luu, _gram(zs, qs, amp, precision), precision)
        Qb = _solve(LB, Qu, precision)
    mean = jnp.dot(Qb.T, c, precision=precision)
    var = amp - jnp.sum(Qu * Qu, axis=0) + jnp.sum(Qb * Qb, axis=0)
    return mean, jnp.sqrt(jnp.maximum(var, 1e-10))


def fit_nll(raw: Dict, x, y, mask, precision: str) -> float:
    """The fit objective on the rows the fit used (padding stripped)."""
    keep = np.asarray(mask) > 0.5
    xr, yr = np.asarray(x)[keep], np.asarray(y)[keep]
    n = xr.shape[0]
    pad = _pad_to(n)
    xp = np.zeros((pad, xr.shape[1]), np.float32)
    yp = np.zeros((pad,), np.float32)
    mp = np.zeros((pad,), np.float32)
    xp[:n], yp[:n], mp[:n] = xr, yr, 1.0
    return float(neg_mll(_raw32(raw), xp, yp, mp, precision=precision))


# The fit's Adam step: moment decays, epsilon and the hyperparameters' clamps
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
CLAMPS = {"log_amp": (-4.0, 4.0),
          "log_ell": (float(np.log(0.01)), float(np.log(10.0))),
          "log_noise": (-9.0, 0.0)}


@functools.partial(jax.jit, static_argnames=("precision",))
def _adam_step(raw, m, v, x, y, mask, bc1, bc2, lr_t, *, precision):
    g = jax.grad(lambda r: neg_mll(r, x, y, mask, precision=precision))(raw)
    # NaN entries are zeroed, as the fit's step does: the plain formula's
    # gradient of log_ell is NaN (sqrt at the zero self-distances)
    g = {k: jnp.nan_to_num(g[k], nan=0.0, posinf=0.0, neginf=0.0) for k in g}
    new, new_m, new_v = {}, {}, {}
    for k in raw:
        new_m[k] = ADAM_B1 * m[k] + (1 - ADAM_B1) * g[k]
        new_v[k] = ADAM_B2 * v[k] + (1 - ADAM_B2) * g[k] * g[k]
        step = lr_t * (new_m[k] / bc1) / (jnp.sqrt(new_v[k] / bc2) + ADAM_EPS)
        new[k] = jnp.clip(raw[k] - step, *CLAMPS[k])
    return new, new_m, new_v, g


def adam_path(fit: Dict, schedule: List[tuple], precision: str):
    """The fit's first Adam steps from the program's starting state and
    data, one per (bc1, bc2, lr_t) of ``schedule``: (hyperparameters after
    each step, the gradient at the start), as float64 arrays."""
    keep = np.asarray(fit["mask"]) > 0.5
    xr = np.asarray(fit["x"])[keep]
    n = xr.shape[0]
    pad = _pad_to(n)
    xp = np.zeros((pad, xr.shape[1]), np.float32)
    yp = np.zeros((pad,), np.float32)
    mp = np.zeros((pad,), np.float32)
    xp[:n], yp[:n], mp[:n] = xr, np.asarray(fit["y"])[keep], 1.0
    raw, m, v = _raw32(fit["raw"]), _raw32(fit["m"]), _raw32(fit["v"])
    as64 = lambda t: {k: np.asarray(t[k], np.float64) for k in t}  # noqa: E731
    path, grad0 = [], None
    for bc1, bc2, lr_t in schedule:
        raw, m, v, g = _adam_step(raw, m, v, xp, yp, mp, np.float32(bc1),
                                  np.float32(bc2), np.float32(lr_t),
                                  precision=precision)
        grad0 = as64(g) if grad0 is None else grad0
        path.append(as64(raw))
    return path, grad0


def _raw32(raw: Dict) -> Dict:
    return {k: jnp.asarray(np.asarray(v, np.float32))
            for k, v in raw.items()}


def acquisition(kind: str, raw: Dict, x, y, fantasy_x, y_pend, pool,
                picks: List[int], precision: str,
                z: Optional[np.ndarray] = None) -> List[np.ndarray]:
    """The UCB over the pool that each batch member was picked from.

    Member b sees the design, the pending trials at their fantasized values
    and members 0..b-1 (the program's picks) at this posterior's mean."""
    x = np.asarray(x, np.float32)
    y = np.asarray(y, np.float32)
    if fantasy_x is not None and len(fantasy_x):
        x = np.vstack([x, np.asarray(fantasy_x, np.float32)])
        y = np.concatenate([y, np.asarray(y_pend, np.float32)])
    pool = np.asarray(pool, np.float32)
    n0, d = x.shape
    rows = _pad_to(n0 + len(picks))
    xp = np.zeros((rows, d), np.float32)
    yp = np.zeros((rows,), np.float32)
    mp = np.zeros((rows,), np.float32)
    xp[:n0], yp[:n0], mp[:n0] = x, y, 1.0
    qp = np.zeros((_pad_to(len(pool)), d), np.float32)
    qp[:len(pool)] = pool
    raw = _raw32(raw)
    out = []
    for b, pick in enumerate(picks):
        if kind == "dense":
            mean, std = dense_pool(raw, xp, yp, mp, qp, precision=precision)
        else:
            mean, std = sparse_pool(raw, z, xp, yp, mp, qp,
                                    precision=precision)
        mean = np.asarray(mean, np.float64)[:len(pool)]
        std = np.asarray(std, np.float64)[:len(pool)]
        out.append(mean + BETA * std)
        row = n0 + b
        xp[row], yp[row], mp[row] = pool[pick], np.float32(mean[pick]), 1.0
    return out
