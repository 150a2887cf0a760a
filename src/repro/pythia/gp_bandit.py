"""Gaussian-Process bandit policy in JAX (paper Code Block 2).

Pipeline per suggestion operation (the Policy's lifespan):
  1. PolicySupporter loads completed trials.
  2. Featurize into [0,1]^d (scaling-aware; one-hot categoricals).
  3. Fit GP hyperparameters (ARD Matérn-5/2 + noise) by maximizing the log
     marginal likelihood with Adam (jax.grad), resuming a persisted Adam
     trajectory when one is stored (paper §6.3). Multi-metric studies fit
     one GP per metric in lockstep through ONE vmapped Adam step per
     iteration (``MultiMetricGP``), sharing the bucket-padded design.
  4. Maximize UCB over scrambled-Halton candidates + local perturbations of
     the incumbent; fantasize pending trials to avoid duplicate suggestions
     when ObservationNoise is LOW (paper Appendix B.2). Multi-metric
     studies maximize the hypervolume-scalarized UCB instead — random
     positive weights per batch member, reference point anchored below the
     observed Pareto frontier (``_suggest_multi``).

Acquisition runs on the factorized-posterior engine
(``repro.pythia.posterior.CholeskyPosterior``): K(X, X) is factorized ONCE
per suggest operation right after the fit, every mean/std/UCB query is
served from the cached (L, w), pending fantasies and batch members extend
the factor with O(n^2) rank-1 appends, and all shapes are padded to
power-of-two buckets so the jitted kernels stop retracing across
operations. Stack-level means go through the fused ``matern52_gram_matvec``
kernel — all levels batched into one device call, no (n, m) cross-Gram
materialization. The pre-engine path (one full Cholesky per batch member
inside jitted ``_ucb``/``_posterior``) is kept behind
``GPBanditPolicy(use_engine=False)`` as the numerical oracle and the
baseline for ``make bench-acquisition``; ``ucb_reference`` keeps the
per-candidate loop purely as the equivalence oracle for tests.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.metadata import Metadata, MetadataDelta
from repro.core.pareto import default_reference_point, pareto_frontier_indices
from repro.core.study import TrialSuggestion
from repro.core.study_config import ObservationNoise, StudyConfig
from repro.kernels import ops as kops
from repro.pythia import halton
from repro.pythia.converters import (
    TrialToArrayConverter,
    align_prior_trials,
    trials_to_xy,
)
from repro.pythia.policy import (
    EarlyStopDecision,
    EarlyStopDecisions,
    EarlyStopRequest,
    Policy,
    PolicySupporter,
    SuggestDecision,
    SuggestRequest,
)
from repro.pythia.posterior import (
    CholeskyPosterior,
    pool_bucket,
    train_bucket,
)
from repro.pythia.sparse_posterior import (
    N_INDUCING,
    SPARSE_THRESHOLD,
    SparsePosterior,
)
from repro.pythia.state import (
    PolicyState,
    load_metric_states,
    load_prior_levels,
    load_state,
    store_state,
)

jax.config.update("jax_enable_x64", False)

# acquisition exploration weight (GaussianProcessBandit's default; the
# policy reads it here instead of constructing a throwaway instance)
DEFAULT_UCB_BETA = 1.8

# Weight of the linear augmentation term in the hypervolume scalarization:
# s_w(u) = min_j((u_j - ref_j)/w_j) + HV_AUGMENT * mean_j((u_j - ref_j)/w_j).
# The min alone is flat wherever one metric's UCB pins the scalarization;
# the small averaged term breaks those ties toward candidates that improve
# the OTHER metrics too (the augmented-Chebyshev trick).
HV_AUGMENT = 0.05

# Above SPARSE_THRESHOLD design rows the hyperparameter fit (Adam on the
# MLL) runs on this many evenly-strided rows instead of the full design —
# the fit cost stays bounded as the study grows, while the posterior itself
# still conditions on every observation through the inducing factorization.
FIT_SUBSAMPLE = 256

# Resumed (warm-started) sparse-path fits are capped at this many Adam
# steps per operation: the persisted trajectory sits at the optimum and
# only needs to track the slow drift of the label renormalization, but an
# uncapped resume occasionally burns 30+ steps chasing that drift and
# blows the large-n per-op latency budget (each step pays a fused
# grad+update dispatch whose cholesky dominates). Unconverged ops hand the
# trajectory to the next op via the persisted state, so the cap bounds
# per-op work without capping total optimization. Cold fits keep the full
# budget.
SPARSE_WARM_FIT_STEPS = 6


def _fit_subsample_idx(n: int) -> np.ndarray:
    """Deterministic evenly-strided row subsample for the sparse-path fit.

    The stride is floor(n / FIT_SUBSAMPLE), so the selected rows are
    IDENTICAL across consecutive operations while the study grows within a
    stride bucket — the warm-started fit re-converges in a couple of steps
    instead of chasing a subsample that shifts under it on every op.
    """
    stride = max(1, n // FIT_SUBSAMPLE)
    idx = np.arange(FIT_SUBSAMPLE, dtype=np.int64) * stride
    return idx[idx < n]


@dataclasses.dataclass
class GPParams:
    log_amp: jnp.ndarray      # ()
    log_ell: jnp.ndarray      # (d,)
    log_noise: jnp.ndarray    # ()


def _kernel(params: GPParams, x1: jnp.ndarray, x2: jnp.ndarray) -> jnp.ndarray:
    ell = jnp.exp(params.log_ell)
    amp = jnp.exp(params.log_amp)
    # impl="auto": Pallas kernel on TPU, XLA reference elsewhere; pools with
    # >= 4096 rows go through the blocked column-strip path either way.
    return kops.matern52_gram(x1 / ell, x2 / ell, amp, impl="auto")


@jax.jit
def _neg_mll(raw: dict, x: jnp.ndarray, y: jnp.ndarray,
             mask: jnp.ndarray) -> jnp.ndarray:
    """Masked negative log marginal likelihood over a bucket-padded design.

    Padding rows (mask 0, y 0) contribute an identity block to K, zero to
    the quadratic form and zero to the log-determinant, so the value differs
    from the unpadded MLL only in nothing at all — while the (x, y) shapes
    stay constant across trial counts within a bucket (no retrace per op).
    """
    params = GPParams(**raw)
    noise = jnp.exp(params.log_noise) + 1e-4
    K = _kernel(params, x, x) * (mask[:, None] * mask[None, :])
    K = K + jnp.diag(noise * mask + (1.0 - mask))
    L = jnp.linalg.cholesky(K)
    alpha = jax.scipy.linalg.cho_solve((L, True), y)
    mll = (
        -0.5 * jnp.dot(y, alpha)
        - jnp.sum(jnp.log(jnp.diagonal(L)))
        - 0.5 * jnp.sum(mask) * jnp.log(2.0 * jnp.pi)
    )
    # weak log-normal priors keep hyperparameters sane on tiny datasets
    prior = (
        -0.5 * (params.log_amp**2)
        - 0.5 * jnp.sum((params.log_ell - jnp.log(0.3)) ** 2)
        - 0.5 * ((params.log_noise - jnp.log(1e-2)) ** 2) / 4.0
    )
    return -(mll + prior)


_mll_grad = jax.jit(jax.value_and_grad(_neg_mll))

# convergence check: one fused kernel per step instead of ~6 host-dispatched
# ops (the fit loop is the suggest hot path)
_step_norm = jax.jit(lambda a, b: jnp.sqrt(sum(
    jnp.sum((x - y) ** 2)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))))


@jax.jit
def _fit_step(raw, m, v, x, y, mask, bc1, bc2, lr_t):
    """One fused Adam step on the negative MLL: grad + moment update +
    clamped parameter step + convergence norm in a single device dispatch.

    The Python loop used to issue ~20 tiny jax ops and 2 host syncs per
    step, which dominated warm-fit latency at large n. ``bc1``/``bc2`` are
    the host-computed bias corrections (1 - beta**t) and ``lr_t`` the
    decayed learning rate — value changes don't retrace. Returns the
    updated (raw, m, v) plus a stacked [loss, step_norm] pair so the caller
    pays ONE transfer per step; on a non-finite loss the caller discards
    the returned state, preserving the old break-before-update semantics.
    """
    b1, b2, eps = 0.9, 0.999, 1e-8
    loss, g = jax.value_and_grad(_neg_mll)(raw, x, y, mask)
    g = jax.tree.map(lambda gg: jnp.nan_to_num(gg, nan=0.0,
                                               posinf=0.0, neginf=0.0), g)
    m = jax.tree.map(lambda mm, gg: b1 * mm + (1 - b1) * gg, m, g)
    v = jax.tree.map(lambda vv, gg: b2 * vv + (1 - b2) * gg * gg, v, g)
    mhat = jax.tree.map(lambda mm: mm / bc1, m)
    vhat = jax.tree.map(lambda vv: vv / bc2, v)
    new_raw = jax.tree.map(
        lambda p, mm, vv: p - lr_t * mm / (jnp.sqrt(vv) + eps),
        raw, mhat, vhat)
    # clamp to numerically-safe ranges (f32 cholesky)
    new_raw = {
        "log_amp": jnp.clip(new_raw["log_amp"], -4.0, 4.0),
        "log_ell": jnp.clip(new_raw["log_ell"], jnp.log(0.01), jnp.log(10.0)),
        "log_noise": jnp.clip(new_raw["log_noise"], -9.0, 0.0),
    }
    norm = jnp.sqrt(sum(
        jnp.sum((a - b) ** 2)
        for a, b in zip(jax.tree.leaves(new_raw), jax.tree.leaves(raw))))
    return new_raw, m, v, jnp.stack([loss, norm])


@jax.jit
def _posterior(raw: dict, x: jnp.ndarray, y: jnp.ndarray, xq: jnp.ndarray):
    params = GPParams(**raw)
    n = x.shape[0]
    noise = jnp.exp(params.log_noise) + 1e-4
    K = _kernel(params, x, x) + noise * jnp.eye(n)
    L = jnp.linalg.cholesky(K)
    alpha = jax.scipy.linalg.cho_solve((L, True), y)
    Kq = _kernel(params, x, xq)  # (n, m)
    mean = Kq.T @ alpha
    vsolve = jax.scipy.linalg.solve_triangular(L, Kq, lower=True)  # (n, m)
    var = jnp.exp(params.log_amp) - jnp.sum(vsolve * vsolve, axis=0)
    return mean, jnp.sqrt(jnp.maximum(var, 1e-10))


def _ucb_from_posterior(raw: dict, x, y, xq, beta) -> jnp.ndarray:
    mean, std = _posterior(raw, x, y, xq)
    return mean + beta * std


# Pre-engine pool scoring: one full Cholesky per call. Kept as the legacy
# baseline (use_engine=False) and the oracle behind ``ucb_reference``.
_ucb = jax.jit(_ucb_from_posterior)

# Fantasized UCB: vmap over F fantasy outcome vectors for the SAME design
# matrix (x augmented with pending points) — shape (F, n_aug) in, (F, m)
# scores out, one batched Cholesky per fantasy instead of a Python loop.
_ucb_fantasy_vmap = jax.jit(
    jax.vmap(_ucb_from_posterior, in_axes=(None, None, 0, None, None))
)


@dataclasses.dataclass
class FitInfo:
    """Observability + resume record of one fit() call.

    ``result`` is the returned (best-loss) hyperparameters; ``raw``/``m``/
    ``v``/``t`` are the Adam trajectory end-point a later fit can resume from
    (after a divergence they are reset to the best point with cold moments,
    so a poisoned trajectory is never persisted).
    """

    result: dict
    raw: dict
    m: dict
    v: dict
    t: int
    steps_run: int
    warm: bool
    converged: bool
    diverged: bool


class GaussianProcessBandit:
    """Stateless-per-call GP regressor + UCB acquisition.

    ``fit(x, y, init=state.fit_init())`` resumes Adam from a persisted
    trajectory (paper §6.3 state saving): steps past the cold budget use a
    1/sqrt(t) learning-rate decay so the resumed trajectory actually settles,
    and the fit exits as soon as the *effective* gradient norm — the Adam-
    preconditioned, clamp-projected step divided by the learning rate —
    drops under ``grad_tol``. The projection matters: on noiseless data the
    MLL pins log_noise to its clamp boundary where the raw gradient stays
    large forever, yet the parameters cannot move; the projected norm goes to
    zero there. A converged warm start costs one gradient evaluation instead
    of ``fit_steps``; a cold fit's first ``fit_steps`` steps are
    bit-identical to the pre-warm-start behavior unless it genuinely plateaus
    below ``grad_tol`` (cold trajectories sit well above it in practice).

    The design matrix is bucket-padded (``posterior.train_bucket``) with
    noise-masked rows before entering the jitted MLL, so the Adam loop
    compiles once per bucket instead of once per trial count.
    """

    def __init__(self, dim: int, *, fit_steps: int = 60, lr: float = 0.08,
                 ucb_beta: float = DEFAULT_UCB_BETA, seed: int = 0,
                 grad_tol: float = 0.01):
        self.dim = dim
        self.fit_steps = fit_steps
        self.lr = lr
        self.ucb_beta = ucb_beta
        self.seed = seed
        self.grad_tol = grad_tol
        self.last_fit: Optional[FitInfo] = None

    def _cold_init(self):
        raw = {
            "log_amp": jnp.asarray(0.0),
            "log_ell": jnp.full((self.dim,), jnp.log(0.3)),
            "log_noise": jnp.asarray(jnp.log(1e-2)),
        }
        return raw, jax.tree.map(jnp.zeros_like, raw), jax.tree.map(jnp.zeros_like, raw), 0

    @staticmethod
    def _tree_f32(tree: Dict) -> dict:
        return {k: jnp.asarray(v, jnp.float32) for k, v in tree.items()}

    def fit(self, x: np.ndarray, y: np.ndarray,
            init: Optional[Dict] = None) -> dict:
        """Returns raw GP hyperparameters after Adam on the marginal likelihood.

        ``init`` (optional) is a PolicyState.fit_init() dict: raw params plus
        Adam moments and step count; the optimizer resumes mid-trajectory.
        """
        n, d = np.asarray(x).shape
        bucket = train_bucket(n)
        xb = np.zeros((bucket, d), np.float32)
        yb = np.zeros((bucket,), np.float32)
        mb = np.zeros((bucket,), np.float32)
        xb[:n], yb[:n], mb[:n] = x, y, 1.0
        x = jnp.asarray(xb)
        y = jnp.asarray(yb)
        mask = jnp.asarray(mb)
        warm = init is not None
        if warm:
            raw = self._tree_f32(init["raw"])
            m = self._tree_f32(init["adam_m"])
            v = self._tree_f32(init["adam_v"])
            t0 = int(init["adam_t"])
        else:
            raw, m, v, t0 = self._cold_init()
        b1, b2 = 0.9, 0.999  # mirrored in _fit_step (eps lives there too)
        best_raw, best_loss = raw, float("inf")
        steps = 0
        converged = diverged = False
        loss = float("inf")
        for t in range(t0 + 1, t0 + self.fit_steps + 1):
            # resumed steps (past the cold budget) decay the lr so the
            # trajectory settles instead of orbiting the optimum forever
            lr_t = self.lr if t <= self.fit_steps else (
                self.lr * (self.fit_steps / t) ** 0.5)
            new_raw, new_m, new_v, stats = _fit_step(
                raw, m, v, x, y, mask, 1 - b1**t, 1 - b2**t, lr_t)
            steps += 1
            loss, norm = (float(s) for s in np.asarray(stats))
            if not np.isfinite(loss):  # singular cholesky: keep best-so-far
                raw = best_raw         # (discard the device-side update)
                diverged = True
                break
            if loss < best_loss:
                best_loss, best_raw = loss, raw
            raw, m, v = new_raw, new_m, new_v
            if self.grad_tol > 0.0:
                # effective gradient: the clamp-projected step / lr
                if norm < self.grad_tol * lr_t:
                    converged = True  # plateaued: stop descending
                    break
        if diverged:
            if not np.isfinite(best_loss):
                # diverged before ANY finite loss: a warm restore point that
                # is singular on the current data. Fall back to the cold
                # init so the persisted checkpoint self-heals instead of
                # pinning every future fit to the same poisoned point.
                best_raw, _, _, _ = self._cold_init()
                raw = best_raw
            result = raw  # already best_raw
            traj_raw, traj_m, traj_v, traj_t = best_raw, \
                jax.tree.map(jnp.zeros_like, best_raw), \
                jax.tree.map(jnp.zeros_like, best_raw), 0
        elif converged:
            result = raw if loss <= best_loss else best_raw
            traj_raw, traj_m, traj_v, traj_t = raw, m, v, t0 + steps
        else:
            final_loss = float(_mll_grad(raw, x, y, mask)[0])
            if not np.isfinite(final_loss):
                # the never-evaluated post-update end-point is singular:
                # persist the best point with cold moments, exactly like the
                # diverged branch, so the poisoned trajectory never resumes
                raw = best_raw
                traj_raw, traj_m, traj_v, traj_t = best_raw, \
                    jax.tree.map(jnp.zeros_like, best_raw), \
                    jax.tree.map(jnp.zeros_like, best_raw), 0
            else:
                traj_raw, traj_m, traj_v, traj_t = raw, m, v, t0 + steps
                if final_loss > best_loss:
                    raw = best_raw
            result = raw
        self.last_fit = FitInfo(
            result=result, raw=traj_raw, m=traj_m, v=traj_v, t=traj_t,
            steps_run=steps, warm=warm, converged=converged, diverged=diverged,
        )
        return result

    def ucb(self, raw: dict, x, y, xq) -> jnp.ndarray:
        """UCB scores for the full candidate pool in one vectorized call."""
        return _ucb(raw, jnp.asarray(x, jnp.float32),
                    jnp.asarray(y, jnp.float32), jnp.asarray(xq, jnp.float32),
                    jnp.float32(self.ucb_beta))

    def ucb_fantasized(self, raw: dict, x, y, pending_x, xq,
                       rng: np.random.RandomState, *, n_fantasies: int = 4
                       ) -> jnp.ndarray:
        """UCB averaged over fantasy outcomes for pending trials.

        Draws ``n_fantasies`` outcome vectors for the pending points from the
        current posterior, augments the training set with each, and scores
        the whole candidate pool under every fantasy via one vmapped batched
        solve — qUCB-style duplicate avoidance without a per-fantasy loop.
        """
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        pend = jnp.asarray(pending_x, jnp.float32)
        xq = jnp.asarray(xq, jnp.float32)
        mean_p, std_p = _posterior(raw, x, y, pend)
        eps = jnp.asarray(rng.randn(n_fantasies, pend.shape[0]), jnp.float32)
        y_fant = jnp.concatenate(
            [jnp.broadcast_to(y, (n_fantasies,) + y.shape),
             mean_p[None, :] + std_p[None, :] * eps],
            axis=1,
        )  # (F, n + p)
        x_aug = jnp.concatenate([x, pend], axis=0)
        scores = _ucb_fantasy_vmap(raw, x_aug, y_fant, xq,
                                   jnp.float32(self.ucb_beta))  # (F, m)
        return jnp.mean(scores, axis=0)

    def ucb_reference(self, raw: dict, x, y, xq) -> np.ndarray:
        """Per-candidate loop oracle for the vectorized path (tests only)."""
        out = np.empty((len(xq),), np.float32)
        for i in range(len(xq)):
            out[i] = float(
                _ucb(raw, jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32),
                     jnp.asarray(xq[i:i + 1], jnp.float32),
                     jnp.float32(self.ucb_beta))[0]
            )
        return out


# Multi-metric fit kernels: the SAME `_fit_step` / `_neg_mll` bodies vmapped
# over a leading metric axis. raw/adam moments/labels are batched (k, ...);
# the design, mask and Adam schedule scalars are shared. One device dispatch
# advances every metric's Adam trajectory one step, and the compiled program
# depends only on (k, bucket) — a study's k is fixed, so steady-state multi-
# metric ops compile exactly as often as single-objective ones.
_fit_step_metrics = jax.jit(jax.vmap(
    _fit_step, in_axes=(0, 0, 0, None, 0, None, None, None, None)))
_neg_mll_metrics = jax.jit(jax.vmap(_neg_mll, in_axes=(0, None, 0, None)))


def _stack_trees(trees: Sequence[Dict]) -> dict:
    """k per-metric hyperparameter trees -> one tree with a leading k axis."""
    return {key: jnp.stack([jnp.asarray(t[key], jnp.float32) for t in trees])
            for key in ("log_amp", "log_ell", "log_noise")}


def _unstack_tree(tree: Dict, k: int) -> List[dict]:
    """Leading-axis tree -> k per-metric trees (device views, no copies)."""
    return [{key: tree[key][i] for key in tree} for i in range(k)]


def _tree_where(cond_k: jnp.ndarray, a: Dict, b: Dict) -> dict:
    """Per-metric tree select: ``cond_k`` is a (k,) bool mask broadcast over
    each leaf's trailing dims (leaves carry the leading metric axis)."""
    def sel(x, y):
        c = cond_k.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.where(c, x, y)
    return {key: sel(a[key], b[key]) for key in a}


@dataclasses.dataclass
class MultiFitInfo:
    """Observability + resume record of one MultiMetricGP.fit call.

    Per-metric lists are metric-ordered; ``t`` is the SHARED Adam clock (all
    metrics step in lockstep through the vmapped kernel). Same best-vs-
    trajectory split as ``FitInfo``: ``results`` are the returned best-loss
    hyperparameters, ``raws``/``ms``/``vs``/``t`` the resumable trajectory.
    """

    results: List[dict]
    raws: List[dict]
    ms: List[dict]
    vs: List[dict]
    t: int
    steps_run: int
    warm: bool
    converged: bool
    diverged: bool


class MultiMetricGP:
    """k independent GPs (one per objective metric) fitted in lockstep.

    Fitting k metrics used to mean k sequential Adam loops — k compiled-
    kernel invocations and k host syncs per step. Here every metric shares
    the engine's bucket-padded design and advances through ONE vmapped
    ``_fit_step`` dispatch per step, with a single stacked (k, 2) loss/norm
    transfer. Divergence and best-loss tracking are per metric (a singular
    Cholesky in one metric's trajectory restores THAT metric to its best
    point — or the cold init — without discarding the others); the loop
    exits when every metric's projected step norm is under ``grad_tol``.

    ``fit`` consumes/produces per-metric hyperparameter trees so each
    metric's ``CholeskyPosterior``/``SparsePosterior`` conditions with its
    own kernel, while the schema-v4 checkpoint resumes all k trajectories
    from one shared Adam clock.
    """

    def __init__(self, dim: int, k: int, *, fit_steps: int = 60,
                 lr: float = 0.08, seed: int = 0, grad_tol: float = 0.01):
        self.dim = dim
        self.k = k
        self.fit_steps = fit_steps
        self.lr = lr
        self.seed = seed
        self.grad_tol = grad_tol
        self.last_fit: Optional[MultiFitInfo] = None

    def _cold_stack(self):
        single = {
            "log_amp": jnp.asarray(0.0),
            "log_ell": jnp.full((self.dim,), jnp.log(0.3)),
            "log_noise": jnp.asarray(jnp.log(1e-2)),
        }
        raw = _stack_trees([single] * self.k)
        zeros = {key: jnp.zeros_like(v) for key, v in raw.items()}
        return raw, zeros, dict(zeros), 0

    def fit(self, x: np.ndarray, y: np.ndarray,
            init: Optional[Dict] = None) -> List[dict]:
        """Per-metric raw hyperparameters after the lockstep Adam fit.

        ``y`` is (n, k), each column already z-scored by the caller. ``init``
        (optional) is ``PolicyState.metric_fit_init()``: per-metric raw
        params + Adam moments and the shared step count.
        """
        n, d = np.asarray(x).shape
        bucket = train_bucket(n)
        xb = np.zeros((bucket, d), np.float32)
        yb = np.zeros((self.k, bucket), np.float32)
        mb = np.zeros((bucket,), np.float32)
        xb[:n] = x
        yb[:, :n] = np.asarray(y, np.float32).T
        mb[:n] = 1.0
        x = jnp.asarray(xb)
        yk = jnp.asarray(yb)
        mask = jnp.asarray(mb)
        warm = init is not None
        if warm:
            raw = _stack_trees(init["raws"])
            m = _stack_trees(init["adam_m"])
            v = _stack_trees(init["adam_v"])
            t0 = int(init["adam_t"])
        else:
            raw, m, v, t0 = self._cold_stack()
        b1, b2 = 0.9, 0.999  # mirrored in _fit_step (eps lives there too)
        cold_raw, _zm, _zv, _zt = self._cold_stack()
        best_raw = raw
        best_loss = np.full((self.k,), np.inf)
        losses = np.full((self.k,), np.inf)
        steps = 0
        converged = diverged = False
        for t in range(t0 + 1, t0 + self.fit_steps + 1):
            lr_t = self.lr if t <= self.fit_steps else (
                self.lr * (self.fit_steps / t) ** 0.5)
            new_raw, new_m, new_v, stats = _fit_step_metrics(
                raw, m, v, x, yk, mask, 1 - b1**t, 1 - b2**t, lr_t)
            steps += 1
            stats = np.asarray(stats)           # (k, 2): ONE transfer/step
            losses, norms = stats[:, 0], stats[:, 1]
            if not np.all(np.isfinite(losses)):
                # a singular cholesky in >=1 metric: keep best-so-far
                # everywhere (discard the whole device-side update — the
                # shared clock means partial acceptance would deschedule)
                raw = best_raw
                diverged = True
                break
            improved = losses < best_loss
            if improved.any():
                best_raw = _tree_where(jnp.asarray(improved), raw, best_raw)
                best_loss = np.where(improved, losses, best_loss)
            raw, m, v = new_raw, new_m, new_v
            if self.grad_tol > 0.0 and np.all(norms < self.grad_tol * lr_t):
                converged = True  # every metric plateaued
                break
        if diverged:
            # metrics that never saw a finite loss self-heal to the cold init
            ok = jnp.asarray(np.isfinite(best_loss))
            best_raw = _tree_where(ok, best_raw, cold_raw)
            result = best_raw
            zeros = {key: jnp.zeros_like(val) for key, val in best_raw.items()}
            traj_raw, traj_m, traj_v, traj_t = best_raw, zeros, dict(zeros), 0
        elif converged:
            result = _tree_where(jnp.asarray(losses <= best_loss),
                                 raw, best_raw)
            traj_raw, traj_m, traj_v, traj_t = raw, m, v, t0 + steps
        else:
            final = np.asarray(_neg_mll_metrics(raw, x, yk, mask))
            if not np.all(np.isfinite(final)):
                # never-evaluated post-update end-point singular somewhere:
                # persist best points with cold moments (see the single-
                # objective fit for the rationale)
                ok = jnp.asarray(np.isfinite(best_loss))
                best_raw = _tree_where(ok, best_raw, cold_raw)
                raw = best_raw
                zeros = {key: jnp.zeros_like(val)
                         for key, val in best_raw.items()}
                traj_raw, traj_m, traj_v, traj_t = best_raw, zeros, \
                    dict(zeros), 0
                result = raw
            else:
                traj_raw, traj_m, traj_v, traj_t = raw, m, v, t0 + steps
                result = _tree_where(jnp.asarray(final <= best_loss),
                                     raw, best_raw)
        self.last_fit = MultiFitInfo(
            results=_unstack_tree(result, self.k),
            raws=_unstack_tree(traj_raw, self.k),
            ms=_unstack_tree(traj_m, self.k),
            vs=_unstack_tree(traj_v, self.k),
            t=traj_t, steps_run=steps, warm=warm, converged=converged,
            diverged=diverged,
        )
        return self.last_fit.results


@jax.jit
def _stack_means(raw_stack: dict, xs: jnp.ndarray, alphas: jnp.ndarray,
                 xq: jnp.ndarray) -> jnp.ndarray:
    """Summed posterior means of a level stack in ONE device call.

    ``raw_stack`` leaves carry a leading level axis; ``xs`` (levels, B, d)
    and ``alphas`` (levels, B) are bucket-padded with zero alpha on padding,
    so padded rows contribute exactly nothing. Each level is a fused
    ``matern52_gram_matvec`` — the (n, m) cross-Gram is never materialized
    and there is no per-level host sync.
    """
    total = jnp.zeros((xq.shape[0],), jnp.float32)
    for i in range(xs.shape[0]):  # static depth: unrolled into one program
        ell = jnp.exp(raw_stack["log_ell"][i])
        amp = jnp.exp(raw_stack["log_amp"][i])
        total = total + kops.matern52_gram_matvec(
            xs[i] / ell, xq / ell, alphas[i], amp, impl="auto")
    return total


@dataclasses.dataclass
class StackLevel:
    """One fitted level of a residual stack: hyperparameters + the (x, y)
    design it conditions on. ``y`` is already residual to the levels below;
    ``posterior`` is the level's cached factorization (dense Cholesky up to
    ``SPARSE_THRESHOLD`` design rows, SGPR inducing-point above — built once
    at fit time, queries and appends never refactorize). ``mean_x`` /
    ``mean_alpha`` are the MEAN-BASIS arrays feeding the fused stack-mean
    matvec: mean(q) = K(q, mean_x) · mean_alpha. For a dense level that is
    the design itself with K^-1 y weights; for a sparse level it is the
    (n_inducing, d) inducing set with the inducing-basis weights — an O(m)
    contraction per level regardless of trial count. ``x``/``y`` always
    remain the REAL design (incumbent selection reads them)."""

    raw: dict
    x: jnp.ndarray          # (n, d) float32, current study's unit space
    y: jnp.ndarray          # (n,) float32 residual targets
    alpha: jnp.ndarray      # posterior mean weights in the mean basis
    posterior: "CholeskyPosterior | SparsePosterior"
    mean_x: np.ndarray      # (nb, d) mean-basis points (design or Z)
    mean_alpha: np.ndarray  # (nb,) weights: mean(q) = K(q, mean_x)·mean_alpha


def _zscore(y: np.ndarray) -> np.ndarray:
    """Per-study label normalization (each stack level owns its own scale)."""
    return (y - float(np.mean(y))) / float(np.std(y) + 1e-9)


class StackedResidualGP:
    """Sequential residual GP stack for transfer learning (paper's transfer
    capability; stacking per the Vizier GP-bandit design, arXiv:2408.11527).

    ``fit_level`` appends one base GP fitted on the residuals of the stack
    so far: level 0 models the first prior study, level 1 the second prior's
    residual to level 0, ..., and the final level the *current* study's
    residual to everything below. The stacked posterior has mean = sum of
    level means and the TOP level's variance (lower levels act as a learned
    mean prior, they do not inflate predictive uncertainty). Passing
    ``raw=`` reuses persisted hyperparameters (schema v3 per-prior-level
    checkpoints) and skips the Adam fit entirely — the level then costs one
    Cholesky instead of ``fit_steps`` likelihood evaluations.

    Level means are served by one batched ``_stack_means`` call over
    bucket-padded per-level arrays — a single device dispatch regardless of
    stack depth, with no cross-Gram materialization.
    """

    def __init__(self, dim: int, *, seed: int = 0):
        self.dim = dim
        self.seed = seed
        self.levels: List[StackLevel] = []
        self.last_fit: Optional[FitInfo] = None
        self._stacked_cache: Dict[int, tuple] = {}

    @property
    def depth(self) -> int:
        return len(self.levels)

    def _stacked_arrays(self, below: int):
        """Bucket-padded (raw_stack, xs, alphas) for levels[:below], cached
        per depth (rebuilt only when a new level is fitted)."""
        if below not in self._stacked_cache:
            levels = self.levels[:below]
            bucket = max(train_bucket(int(lvl.mean_x.shape[0]))
                         for lvl in levels)
            xs = np.zeros((len(levels), bucket, self.dim), np.float32)
            alphas = np.zeros((len(levels), bucket), np.float32)
            for i, lvl in enumerate(levels):
                n = int(lvl.mean_x.shape[0])
                xs[i, :n] = lvl.mean_x
                alphas[i, :n] = lvl.mean_alpha
            raw_stack = {
                k: jnp.stack([jnp.asarray(lvl.raw[k], jnp.float32)
                              for lvl in levels])
                for k in ("log_amp", "log_ell", "log_noise")
            }
            self._stacked_cache[below] = (
                raw_stack, jnp.asarray(xs), jnp.asarray(alphas))
        return self._stacked_cache[below]

    def mean(self, xq, *, below: Optional[int] = None) -> np.ndarray:
        """Summed posterior mean of the first ``below`` levels (default all)
        at the query points — every level folded into one fused batched
        gram-matvec dispatch (query shapes bucket-padded, so steady-state
        calls never retrace)."""
        below = self.depth if below is None else below
        m = len(xq)
        if below <= 0 or m == 0:
            return np.zeros((m,), np.float32)
        raw_stack, xs, alphas = self._stacked_arrays(below)
        xqp = np.zeros((pool_bucket(m), self.dim), np.float32)
        xqp[:m] = np.asarray(xq, np.float32)
        return np.asarray(
            _stack_means(raw_stack, xs, alphas, jnp.asarray(xqp)))[:m]

    def fit_level(self, x: np.ndarray, y: np.ndarray,
                  init: Optional[Dict] = None, raw: Optional[Dict] = None,
                  capacity: Optional[int] = None) -> dict:
        """Fits the next level on ``y`` minus the stack-so-far mean at ``x``.

        ``y`` must already be label-normalized for its own study. ``raw``
        (persisted v3 prior-level hyperparameters) skips the fit;
        ``capacity`` reserves rank-1 append headroom in the level's cached
        factorization (the policy passes pending + batch count for the
        level that will serve the acquisition). Returns the fitted raw
        hyperparameters; ``last_fit`` carries the FitInfo of the most recent
        *fitted* level (the top level's is what the warm-start checkpoint
        persists).

        Above ``SPARSE_THRESHOLD`` design rows the level goes sparse: the
        hyperparameter fit runs on a deterministic evenly-strided subsample
        (``FIT_SUBSAMPLE`` rows — the MLL stays O(bounded) as the study
        grows) and the cached factorization is the SGPR inducing-point
        posterior instead of the n×n Cholesky. At or below the threshold
        the dense path is bit-for-bit unchanged.
        """
        resid = np.asarray(y, np.float32) - self.mean(x)
        n = int(np.asarray(x).shape[0])
        sparse = n > SPARSE_THRESHOLD
        if raw is None:
            gp = GaussianProcessBandit(dim=self.dim, seed=self.seed)
            if sparse:
                if init is not None:
                    gp.fit_steps = min(gp.fit_steps, SPARSE_WARM_FIT_STEPS)
                idx = _fit_subsample_idx(n)
                raw = gp.fit(np.asarray(x)[idx], resid[idx], init=init)
            else:
                raw = gp.fit(x, resid, init=init)
            self.last_fit = gp.last_fit
        else:
            raw = {k: jnp.asarray(v, jnp.float32) for k, v in raw.items()}
        if sparse:
            post = SparsePosterior(raw, x, resid, n_inducing=N_INDUCING,
                                   seed=self.seed, capacity=capacity)
            mean_x = post.inducing_z
            mean_alpha = np.asarray(post.alpha)
        else:
            post = CholeskyPosterior(raw, x, resid, capacity=capacity)
            mean_x = np.asarray(x, np.float32)
            mean_alpha = np.asarray(post.alpha)[:n]
        # x/y stay host-side: every consumer reads them back as numpy, and a
        # device round-trip of the unpadded (n, d) design would compile a
        # fresh convert_element_type for every distinct n as the study grows.
        self.levels.append(StackLevel(
            raw=raw, x=np.asarray(x, np.float32),
            y=np.asarray(resid, np.float32),
            alpha=post.alpha, posterior=post,
            mean_x=mean_x, mean_alpha=mean_alpha,
        ))
        self._stacked_cache.clear()
        return raw

    def predict(self, xq) -> "tuple[np.ndarray, np.ndarray]":
        """Stacked posterior (mean of all levels, std of the top level) —
        served from the top level's cached factorization, no refit."""
        if not self.levels:
            raise ValueError("predict() on an empty stack")
        m_top, s_top = self.levels[-1].posterior.query(xq)
        return self.mean(xq, below=self.depth - 1) + m_top, s_top


class GPBanditPolicy(Policy):
    """The paper's GP-bandit example as a full Pythia policy.

    With ``warm_start=True`` (default) each suggest operation persists a
    versioned PolicyState record (kernel hyperparameters + Adam trajectory +
    per-prior-level hyperparameters) into the reserved ``repro.gp_bandit``
    study-metadata namespace and resumes the fit from it on the next
    operation — the paper's §6.3 state mechanism applied to the
    hyperparameter optimization. Incompatible or corrupt state silently
    degrades to a cold fit.

    Transfer learning: when the study lists ``prior_study_names``, their
    completed trials are aligned into the current study's feature space
    (``align_prior_trials``) and fitted as a sequential residual stack
    (``StackedResidualGP``) underneath the current study's GP; the
    acquisition maximizes stacked-mean + beta * top-level-std. A prior study
    that is missing, deleted, unreadable, or unalignable is skipped — the
    fully degraded case is exactly the single-study cold fit, never a failed
    operation. With priors present the policy suggests from the stack even
    before ``min_completed`` current trials exist (that head start is the
    point of transfer). Prior-level fits are reused from the persisted v3
    checkpoint for the longest prefix of priors whose aligned-trial
    fingerprints still match (``last_prior_levels_reused``).

    Multi-metric studies are first-class (they used to silently degrade to
    random sampling): ``_suggest_multi`` fits one GP per objective metric —
    all k Adam trajectories advancing through one vmapped step per
    iteration — builds one cached posterior per metric over the shared
    engine buckets, and acquires by hypervolume-scalarized UCB with
    random-weight Chebyshev scalarizations drawn per batch member. State
    persists under schema v4 with per-metric trajectories; transfer
    learning stays single-objective-only (``_load_priors`` skips
    multi-objective studies).

    ``use_engine=False`` switches the single-objective acquisition to the
    pre-engine path — one full Cholesky refactorization per batch member —
    kept as the numerical baseline for tests and ``make bench-acquisition``.
    Both paths share the candidate pool (one scrambled-Halton global half +
    local perturbations of the incumbent, drawn once per operation) and the
    fantasy outcomes, so their suggestions agree trial-for-trial.
    """

    def __init__(self, supporter: PolicySupporter, *, n_candidates: int = 2000,
                 min_completed: int = 5, seed: int = 0, warm_start: bool = True,
                 min_prior_trials: int = 5, use_engine: bool = True,
                 n_fantasies: int = 4):
        self._supporter = supporter
        self._n_candidates = n_candidates
        self._min_completed = min_completed
        self._seed = seed
        self._warm_start = warm_start
        self._min_prior_trials = min_prior_trials
        self._use_engine = use_engine
        self._n_fantasies = n_fantasies
        # per-instance suggest-op counter: part of the acquisition RNG nonce
        # (see suggest()), so repeated ops on ONE policy object never replay
        # the same candidate pool even at a fixed trial count
        self._op_count = 0
        # observability for tests/benchmarks (mirrors
        # SerializableDesignerPolicy.last_restore_was_incremental)
        self.last_fit_steps: int = 0
        self.last_fit_warm: bool = False
        self.last_transfer_levels: int = 0
        self.last_prior_levels_reused: int = 0
        self.last_sparse: bool = False

    def _load_priors(self, request: SuggestRequest,
                     converter: TrialToArrayConverter):
        """[(study name, aligned features, labels)] per usable prior study.

        Defensive end to end: a deleted prior study, a failed multi-read, a
        config that no longer parses, or a trial set that does not align all
        degrade to skipping that prior — never to a failed operation.
        """
        config = request.study_config
        names = [n for n in config.prior_study_names if n != request.study_guid]
        if not names or config.is_multi_objective:
            return []
        try:
            multi = self._supporter.GetTrialsMulti(
                names, status_matches="SUCCEEDED")
        except Exception:  # noqa: BLE001 — one bad prior must not kill all
            multi = {}
        out = []
        for name in names:
            try:
                trials = multi.get(name)
                if trials is None:
                    trials = self._supporter.GetTrials(
                        name, status_matches="SUCCEEDED")
                if len(trials) < self._min_prior_trials:
                    continue
                prior_config = self._supporter.GetStudyConfig(name)
                px, py = align_prior_trials(trials, prior_config, converter)
                if px.shape[0] < self._min_prior_trials:
                    continue
                out.append((name, px, py))
            except Exception:  # noqa: BLE001 — degrade to a colder fit
                continue
        return out

    def _draw_pool(self, rng: np.random.RandomState, dim: int,
                   incumbent: np.ndarray) -> np.ndarray:
        """One candidate pool per suggest operation: a scrambled-Halton
        global half (low-discrepancy, seeded by the op rng) plus local
        perturbations sharpening exploitation around the incumbent."""
        glob = halton.scrambled_halton(self._n_candidates, dim, rng)
        local = np.clip(
            incumbent[None, :]
            + 0.08 * rng.randn(self._n_candidates // 4, dim),
            0.0, 1.0,
        )
        return np.vstack([glob, local])

    def suggest(self, request: SuggestRequest) -> SuggestDecision:
        with tracing.span("vizier.policy.suggest"):
            return self._suggest(request)

    def _suggest(self, request: SuggestRequest) -> SuggestDecision:
        config = request.study_config
        converter = TrialToArrayConverter(config.search_space)
        completed = self._supporter.CompletedTrials(request.study_guid)
        with tracing.span("vizier.policy.featurize"):
            x, y_all = trials_to_xy(completed, config, converter)
        op_nonce = self._op_count
        self._op_count += 1

        priors = self._load_priors(request, converter)
        self.last_transfer_levels = len(priors)
        # reset per-operation observability: a priors-only suggest performs
        # no current-study fit and must not report the previous one's
        self.last_fit_steps, self.last_fit_warm = 0, False
        self.last_prior_levels_reused = 0

        if x.shape[0] < self._min_completed and not priors:
            # cold start: random until enough completed trials to fit
            suggestions = [
                TrialSuggestion(parameters=config.search_space.sample())
                for _ in range(request.count)
            ]
            return SuggestDecision(suggestions=suggestions)

        if config.is_multi_objective:
            return self._suggest_multi(request, config, converter, completed,
                                       x, y_all, op_nonce)

        # pending trials are loaded up front: the top level's factorization
        # reserves rank-1 headroom for their fantasies + the batch members
        pending = self._supporter.ActiveTrials(request.study_guid)
        with tracing.span("vizier.policy.featurize"):
            fantasy_x = converter.to_features(
                [t.parameters for t in pending]) if pending else None
        n_pend = 0 if fantasy_x is None else len(fantasy_x)
        # Acquisition RNG: seeding by completed count ALONE meant consecutive
        # suggest ops at an unchanged completed count replayed the identical
        # Halton scrambling, local perturbations and fantasy draws — repeated
        # suggestions and zero batch diversity until a trial completed. The
        # nonce mixes in the pending count (service-side ops observe the
        # ACTIVE trials earlier suggestions created) and the per-instance op
        # counter (direct back-to-back suggest() calls on one object). Every
        # component is a deterministic function of the observed study
        # snapshot + op index, so identical snapshots still suggest
        # identically across topologies, replays and warm/cold servers.
        rng = np.random.RandomState(
            (self._seed + len(completed) + 1000003 * n_pend
             + 7919 * op_nonce) % (2 ** 32))
        has_current = x.shape[0] >= 1
        headroom = n_pend + request.count

        with tracing.span("vizier.policy.fit") as fit:
            prior_fps = {name: int(px.shape[0]) for name, px, _py in priors}
            reusable: List[Dict] = []
            if self._warm_start and priors:
                reusable = load_prior_levels(
                    request.study_metadata, dim=converter.dim,
                    priors=[(name, int(px.shape[0]))
                            for name, px, _py in priors])
            stack = StackedResidualGP(dim=converter.dim, seed=self._seed)
            for i, (_name, px, py) in enumerate(priors):
                top_prior = (i == len(priors) - 1) and not has_current
                stack.fit_level(
                    px, _zscore(py),
                    raw=reusable[i] if i < len(reusable) else None,
                    capacity=px.shape[0] + headroom if top_prior else None)
            self.last_prior_levels_reused = min(len(reusable), len(priors))

            fit_info = None
            if has_current:
                yn = _zscore(y_all[:, 0])
                state = None
                if self._warm_start:
                    state = load_state(request.study_metadata,
                                       dim=converter.dim,
                                       num_trials=x.shape[0],
                                       prior_fingerprints=prior_fps)
                stack.fit_level(
                    x, yn, init=state.fit_init() if state is not None else None,
                    capacity=x.shape[0] + headroom)
                fit_info = stack.last_fit
                self.last_fit_steps = fit_info.steps_run
                self.last_fit_warm = fit_info.warm
            fit.add(steps=self.last_fit_steps)
        with tracing.span("vizier.policy.acquire"):
            # acquisition works on the TOP level (the current study's
            # residual GP when any current trials exist, else the deepest
            # prior level); the levels below contribute a fixed mean shift.
            top = stack.levels[-1]
            self.last_sparse = isinstance(top.posterior, SparsePosterior)
            raw = top.raw
            n_below = stack.depth - 1
            xs = np.asarray(top.x, np.float64)
            ys = np.asarray(top.y, np.float64)
            mu_xs = stack.mean(xs, below=n_below).astype(np.float64)

            # one candidate pool per operation (incumbent = best STACKED
            # value, not best residual); pending-trial dedup with the
            # empty-pool fallback — a pending trial at every candidate must
            # degrade to the unfiltered pool, never to an argmax over zero
            # candidates
            incumbent = xs[int(np.argmax(ys + mu_xs))]
            pool = self._draw_pool(rng, converter.dim, incumbent)
            fantasize = fantasy_x is not None and n_pend > 0 and (
                config.observation_noise != ObservationNoise.HIGH
            )
            if fantasize:
                d = np.linalg.norm(pool[:, None, :] - fantasy_x[None], axis=-1)
                filtered = pool[np.min(d, axis=1) > 1e-3]
                if len(filtered):
                    pool = filtered
            pool_mu = stack.mean(pool, below=n_below) if n_below else \
                np.zeros((len(pool),), np.float32)

            beta = DEFAULT_UCB_BETA
            y_pend = None
            if fantasize:
                # pending outcomes fantasized from the current posterior; UCB
                # is linear in the mean, so averaging scores over F fantasy
                # vectors equals scoring once at the fantasy-averaged outcomes
                if self._use_engine:
                    mean_p, std_p = top.posterior.query(fantasy_x)
                else:
                    mp, sp = _posterior(raw, jnp.asarray(xs, jnp.float32),
                                        jnp.asarray(ys, jnp.float32),
                                        jnp.asarray(fantasy_x, jnp.float32))
                    mean_p, std_p = np.asarray(mp), np.asarray(sp)
                eps = rng.randn(self._n_fantasies, n_pend)
                y_pend = mean_p + std_p * eps.mean(axis=0)

            if self._use_engine:
                picks = self._suggest_engine(top.posterior, pool, pool_mu, beta,
                                             fantasy_x if fantasize else None,
                                             y_pend, request.count)
            else:
                picks = self._suggest_legacy(raw, xs, ys, pool, pool_mu, beta,
                                             fantasy_x if fantasize else None,
                                             y_pend, request.count)
        with tracing.span("vizier.policy.featurize"):
            suggestions = [
                TrialSuggestion(
                    parameters=converter.to_parameters(p[None, :])[0])
                for p in picks
            ]

        if self._warm_start and fit_info is not None:
            # persist the fit checkpoint so the next (stateless) invocation
            # resumes Adam instead of refitting from scratch. SendMetadata is
            # the single write path: in-process it applies atomically through
            # the datastore, remote it is buffered into the batch response
            # (zero extra wire frames). The decision's own delta stays empty
            # so the service never applies the same checkpoint twice.
            delta = MetadataDelta()
            store_state(delta, PolicyState.from_fit(
                fit_info, dim=converter.dim, num_trials=x.shape[0],
                prior_fingerprints=prior_fps,
                prior_levels=[
                    (name, int(px.shape[0]), stack.levels[i].raw)
                    for i, (name, px, _py) in enumerate(priors)
                ]))
            self._supporter.SendMetadata(delta)
        return SuggestDecision(suggestions=suggestions)

    def _suggest_multi(self, request: SuggestRequest, config: StudyConfig,
                       converter: TrialToArrayConverter, completed,
                       x: np.ndarray, y_all: np.ndarray,
                       op_nonce: int) -> SuggestDecision:
        """Multi-metric acquisition: one GP per metric on the shared engine
        buckets, hypervolume-scalarized UCB over one candidate pool.

        Fit: all k metrics advance through ONE vmapped Adam step per
        iteration (``MultiMetricGP``), warm-started from the schema-v4
        per-metric trajectories. Each metric then gets its own
        ``CholeskyPosterior``/``SparsePosterior`` over the SAME z-scored
        design bucket — identical shapes, so every engine kernel stays on
        its single compiled program regardless of k.

        Acquire: per batch member, draw a positive weight vector w from the
        op RNG (batch diversity comes from the weights, not greedy
        fantasization alone) and maximize the hypervolume scalarization
        s_w(u) = min_j((u_j - ref_j)/w_j) (+ a small averaged term, see
        ``HV_AUGMENT``) of the per-metric UCB vector u over the pool, with
        the reference point anchored below the observed frontier
        (``default_reference_point``). Maximizing E_w[max s_w] targets
        hypervolume improvement (the Vizier GP-bandit scalarization,
        arXiv:2408.11527). Pending trials are fantasized per metric with
        rank-1 appends; picked members fantasize at their per-metric
        posterior means via ``append_pool_member``.
        """
        pending = self._supporter.ActiveTrials(request.study_guid)
        with tracing.span("vizier.policy.featurize"):
            fantasy_x = converter.to_features(
                [t.parameters for t in pending]) if pending else None
        n_pend = 0 if fantasy_x is None else len(fantasy_x)
        # same acquisition-RNG nonce as the single-objective path (see
        # suggest()): deterministic per observed snapshot + op index
        rng = np.random.RandomState(
            (self._seed + len(completed) + 1000003 * n_pend
             + 7919 * op_nonce) % (2 ** 32))
        headroom = n_pend + request.count
        k = len(config.metrics)
        metric_names = [mi.name for mi in config.metrics]
        n = int(x.shape[0])

        # per-metric z-scoring: each objective owns its own scale, so one
        # wide-range metric cannot drown the others in the scalarization
        yz = np.stack([_zscore(y_all[:, j]) for j in range(k)], axis=1)

        with tracing.span("vizier.policy.fit") as fit:
            state = None
            if self._warm_start:
                state = load_metric_states(
                    request.study_metadata, dim=converter.dim, num_trials=n,
                    metric_names=metric_names)
            gp = MultiMetricGP(dim=converter.dim, k=k, seed=self._seed)
            init = state.metric_fit_init() if state is not None else None
            sparse = n > SPARSE_THRESHOLD
            if sparse:
                if init is not None:
                    gp.fit_steps = min(gp.fit_steps, SPARSE_WARM_FIT_STEPS)
                idx = _fit_subsample_idx(n)
                raws = gp.fit(x[idx], yz[idx], init=init)
            else:
                raws = gp.fit(x, yz, init=init)
            fit_info = gp.last_fit
            self.last_fit_steps = fit_info.steps_run
            self.last_fit_warm = fit_info.warm
            self.last_sparse = sparse

            # one posterior per metric over the SAME design rows and
            # capacity: identical bucket shapes -> the engine kernels
            # compiled for metric 0 serve metrics 1..k-1 (and every
            # single-objective study) unchanged
            posts: List = []
            for j in range(k):
                if sparse:
                    posts.append(SparsePosterior(
                        raws[j], x, yz[:, j], n_inducing=N_INDUCING,
                        seed=self._seed, capacity=n + headroom))
                else:
                    posts.append(CholeskyPosterior(
                        raws[j], x, yz[:, j], capacity=n + headroom))
            fit.add(steps=self.last_fit_steps)

        with tracing.span("vizier.policy.acquire"):
            # incumbent frontier + reference point from the OBSERVED (z-scored)
            # objectives; the pool sharpens around a balanced frontier member
            front_idx = pareto_frontier_indices(yz)
            ref = default_reference_point(yz)                     # (k,)
            front = yz[front_idx]
            incumbent = x[front_idx[int(np.argmax(front.sum(axis=1)))]]
            pool = self._draw_pool(rng, converter.dim, incumbent)

            fantasize = fantasy_x is not None and n_pend > 0 and (
                config.observation_noise != ObservationNoise.HIGH
            )
            if fantasize:
                d = np.linalg.norm(pool[:, None, :] - fantasy_x[None], axis=-1)
                filtered = pool[np.min(d, axis=1) > 1e-3]
                if len(filtered):
                    pool = filtered
                # per-metric fantasy outcomes, conditioned with rank-1 appends;
                # ONE eps draw shared across metrics keeps the fantasies
                # consistent (a lucky pending trial is lucky on every metric)
                eps = rng.randn(self._n_fantasies, n_pend).mean(axis=0)
                for post in posts:
                    mean_p, std_p = post.query(fantasy_x)
                    for px, py in zip(fantasy_x, mean_p + std_p * eps):
                        post.append(px, py)

            for post in posts:
                post.set_pool(pool)

            beta = DEFAULT_UCB_BETA
            picks: List[np.ndarray] = []
            picked_idx: List[int] = []
            u = np.empty((k, len(pool)), np.float64)
            for b in range(request.count):
                # random positive scalarization weights per batch member: each
                # member chases a different frontier direction
                w = rng.rand(k) + 1e-3
                w = w / w.sum()
                for j, post in enumerate(posts):
                    mean, std = post.pool_mean_std()   # fused, one sync/metric
                    u[j] = mean + beta * std
                t = (u - ref[:, None]) / w[:, None]
                scores = np.min(t, axis=0) + HV_AUGMENT * np.mean(t, axis=0)
                scores[picked_idx] = -np.inf
                i = int(np.argmax(scores))
                picks.append(pool[i])
                picked_idx.append(i)
                if b + 1 < request.count:
                    # fantasize the member at its posterior mean on EVERY metric
                    for post in posts:
                        post.append_pool_member(i)
        with tracing.span("vizier.policy.featurize"):
            suggestions = [
                TrialSuggestion(
                    parameters=converter.to_parameters(p[None, :])[0])
                for p in picks
            ]

        if self._warm_start and fit_info is not None:
            # schema-v4 checkpoint: metric 0's trajectory doubles as the
            # top-level record (single-blob layout), metric_states carries
            # all k trajectories under the shared Adam clock
            info0 = FitInfo(
                result=fit_info.results[0], raw=fit_info.raws[0],
                m=fit_info.ms[0], v=fit_info.vs[0], t=fit_info.t,
                steps_run=fit_info.steps_run, warm=fit_info.warm,
                converged=fit_info.converged, diverged=fit_info.diverged)
            delta = MetadataDelta()
            store_state(delta, PolicyState.from_fit(
                info0, dim=converter.dim, num_trials=n,
                metric_states=[
                    (metric_names[j], fit_info.raws[j], fit_info.ms[j],
                     fit_info.vs[j])
                    for j in range(k)
                ]))
            self._supporter.SendMetadata(delta)
        return SuggestDecision(suggestions=suggestions)

    def _suggest_engine(self, post: "CholeskyPosterior | SparsePosterior",
                        pool, pool_mu, beta, fantasy_x, y_pend,
                        count: int) -> List[np.ndarray]:
        """Factorized-posterior batch: pending fantasies and picked members
        extend the op's single factorization with rank-1 appends (dense: the
        n×n Cholesky; sparse: the m×m inducing factor); pool scores refresh
        incrementally per member from the cached cross-solve."""
        if fantasy_x is not None:
            for px, py in zip(fantasy_x, y_pend):
                post.append(px, py)
        post.set_pool(pool)
        picks: List[np.ndarray] = []
        picked_idx: List[int] = []
        for k in range(count):
            scores = post.pool_ucb(beta) + pool_mu
            scores[picked_idx] = -np.inf
            i = int(np.argmax(scores))
            picks.append(pool[i])
            picked_idx.append(i)
            if k + 1 < count:
                # fantasize the new member at its posterior mean (read from
                # the cached pool means ON DEVICE) so later members avoid it
                post.append_pool_member(i)
        return picks

    def _suggest_legacy(self, raw, xs, ys, pool, pool_mu, beta, fantasy_x,
                        y_pend, count: int) -> List[np.ndarray]:
        """Pre-engine baseline: one full Cholesky refactorization per batch
        member (plus one per fantasy-mean query) through the jitted
        ``_ucb``/``_posterior`` kernels — identical math, redundant
        factorizations and shape-driven retraces. Kept for
        ``make bench-acquisition`` and the engine-equivalence tests."""
        xs_aug = np.asarray(xs, np.float64)
        ys_aug = np.asarray(ys, np.float64)
        if fantasy_x is not None:
            xs_aug = np.vstack([xs_aug, fantasy_x])
            ys_aug = np.concatenate([ys_aug, y_pend])
        picks: List[np.ndarray] = []
        picked_idx: List[int] = []
        for k in range(count):
            scores = np.asarray(
                _ucb(raw, jnp.asarray(xs_aug, jnp.float32),
                     jnp.asarray(ys_aug, jnp.float32),
                     jnp.asarray(pool, jnp.float32), jnp.float32(beta))
            ) + pool_mu
            scores[picked_idx] = -np.inf
            i = int(np.argmax(scores))
            picks.append(pool[i])
            picked_idx.append(i)
            if k + 1 < count:
                mean, _ = _posterior(raw, jnp.asarray(xs_aug, jnp.float32),
                                     jnp.asarray(ys_aug, jnp.float32),
                                     jnp.asarray(pool[i][None, :], jnp.float32))
                xs_aug = np.vstack([xs_aug, pool[i][None, :]])
                ys_aug = np.concatenate([ys_aug, np.asarray(mean, np.float64)])
        return picks

    def early_stop(self, request: EarlyStopRequest) -> EarlyStopDecisions:
        from repro.core import early_stopping

        config = request.study_config
        all_trials = self._supporter.GetTrials(request.study_guid)
        by_id = {t.id: t for t in all_trials}
        decisions = []
        for tid in request.trial_ids:
            t = by_id.get(tid)
            if t is None:
                decisions.append(EarlyStopDecision(tid, False, "unknown trial"))
                continue
            stop = early_stopping.should_stop(t, all_trials, config)
            decisions.append(
                EarlyStopDecision(tid, stop, "automated stopping rule" if stop else "")
            )
        return EarlyStopDecisions(decisions=decisions)
