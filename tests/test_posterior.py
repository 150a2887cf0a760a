"""Factorized-posterior acquisition engine (repro.pythia.posterior).

Pins the PR's acceptance criteria: cached-posterior and rank-1-updated
scores match the ``ucb_reference`` per-candidate oracle to <= 1e-4, batch
suggestions agree trial-for-trial with the pre-engine path, and the jitted
engine kernels compile at most once across 20 shape-varying suggest
operations (bucket padding kills the per-(n, m) retraces).
"""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import Measurement, ScaleType, StudyConfig, Trial, TrialState
from repro.core.study import Study
from repro.pythia import posterior as post_mod
from repro.pythia.gp_bandit import GaussianProcessBandit, GPBanditPolicy
from repro.pythia.policy import StudyDescriptor, SuggestRequest
from repro.pythia.posterior import (
    CholeskyPosterior,
    TRACE_COUNTS,
    pool_bucket,
    reset_trace_counts,
    train_bucket,
)
from repro.pythia.supporter import DatastorePolicySupporter
from repro.service.datastore import InMemoryDatastore


def _fitted_gp(n=18, d=3, seed=0, fit_steps=30):
    rng = np.random.RandomState(seed)
    gp = GaussianProcessBandit(dim=d, fit_steps=fit_steps)
    x = rng.rand(n, d)
    y = np.sin(2 * x.sum(axis=1)) + 0.05 * rng.randn(n)
    raw = gp.fit(x, y)
    return gp, raw, x, y


def _raw_tree(d, rng):
    return {
        "log_amp": np.float32(rng.uniform(-0.5, 0.5)),
        "log_ell": np.full((d,), np.log(0.3) + rng.uniform(-0.3, 0.3),
                           np.float32),
        "log_noise": np.float32(rng.uniform(-6.0, -3.0)),
    }


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------


def test_bucket_rules():
    assert train_bucket(1) == 64 and train_bucket(64) == 64
    assert train_bucket(65) == 128 and train_bucket(300) == 512
    assert pool_bucket(1) == 256 and pool_bucket(256) == 256
    assert pool_bucket(257) == 512 and pool_bucket(2500) == 2560


# ---------------------------------------------------------------------------
# cached posterior == per-candidate oracle (acceptance: <= 1e-4)
# ---------------------------------------------------------------------------


def test_cached_posterior_matches_ucb_reference_oracle():
    gp, raw, x, y = _fitted_gp()
    rng = np.random.RandomState(1)
    pool = rng.rand(60, x.shape[1])
    post = CholeskyPosterior(raw, x, y)
    post.set_pool(pool)
    oracle = gp.ucb_reference(raw, x, y, pool)
    np.testing.assert_allclose(post.pool_ucb(gp.ucb_beta), oracle,
                               atol=1e-4, rtol=1e-4)


def test_rank1_updated_scores_match_refactorized_oracle():
    """After k rank-1 appends the cached pool scores equal the oracle run
    on the fully refactorized augmented design (acceptance: <= 1e-4)."""
    gp, raw, x, y = _fitted_gp()
    rng = np.random.RandomState(2)
    pool = rng.rand(50, x.shape[1])
    post = CholeskyPosterior(raw, x, y, capacity=len(x) + 6)
    post.set_pool(pool)
    adds_x = rng.rand(6, x.shape[1])
    adds_y = 0.3 * rng.randn(6)
    for ax, ay in zip(adds_x, adds_y):
        post.append(ax, ay)
    x_aug = np.vstack([x, adds_x])
    y_aug = np.concatenate([y, adds_y])
    oracle = gp.ucb_reference(raw, x_aug, y_aug, pool)
    np.testing.assert_allclose(post.pool_ucb(gp.ucb_beta), oracle,
                               atol=1e-4, rtol=1e-4)
    # point queries reuse the same factor
    qm, qs = post.query(pool[:7])
    np.testing.assert_allclose(qm + gp.ucb_beta * qs, oracle[:7],
                               atol=1e-4, rtol=1e-4)


@given(st.integers(min_value=3, max_value=24),
       st.integers(min_value=1, max_value=5),
       st.integers(min_value=1, max_value=5),
       st.integers(min_value=0, max_value=10**6))
@settings(max_examples=15, deadline=None)
def test_cholupdate_equals_full_refactorization_property(n, d, k, seed):
    """Property: a chain of rank-1 appends == one fresh factorization of the
    full design, for random sizes/hyperparameters/data."""
    rng = np.random.RandomState(seed)
    raw = _raw_tree(d, rng)
    x = rng.rand(n, d).astype(np.float32)
    y = rng.randn(n).astype(np.float32)
    adds_x = rng.rand(k, d).astype(np.float32)
    adds_y = rng.randn(k).astype(np.float32)

    incremental = CholeskyPosterior(raw, x, y, capacity=n + k)
    for ax, ay in zip(adds_x, adds_y):
        incremental.append(ax, ay)
    fresh = CholeskyPosterior(raw, np.vstack([x, adds_x]),
                              np.concatenate([y, adds_y]))
    xq = rng.rand(20, d).astype(np.float32)
    m_inc, s_inc = incremental.query(xq)
    m_new, s_new = fresh.query(xq)
    np.testing.assert_allclose(m_inc, m_new, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s_inc, s_new, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(incremental.alpha)[:n + k],
                               np.asarray(fresh.alpha)[:n + k],
                               atol=5e-3, rtol=5e-3)


def test_exact_duplicate_append_matches_fresh_factorization():
    """Regression: appending a point identical to an existing design row
    drove the new Cholesky pivot to the sqrt(1e-10) numerical floor, so the
    whitened observation exploded and every later query/score was corrupt.
    The pivot is now floored at the noise variance (the true Schur
    complement of a duplicate row is ~2*noise), so a duplicate append must
    agree with a fresh factorization of the augmented design."""
    gp, raw, x, y = _fitted_gp(n=12, d=3)
    rng = np.random.RandomState(5)
    dup_x, dup_y = x[4].copy(), float(y[4])

    incremental = CholeskyPosterior(raw, x, y, capacity=x.shape[0] + 1)
    incremental.append(dup_x, dup_y)
    fresh = CholeskyPosterior(raw, np.vstack([x, dup_x[None]]),
                              np.concatenate([y, [dup_y]]))
    xq = rng.rand(30, 3)
    m_inc, s_inc = incremental.query(xq)
    m_new, s_new = fresh.query(xq)
    assert np.isfinite(m_inc).all() and np.isfinite(s_inc).all()
    np.testing.assert_allclose(m_inc, m_new, atol=5e-3, rtol=5e-3)
    np.testing.assert_allclose(s_inc, s_new, atol=5e-3, rtol=5e-3)

    # pool scores survive the duplicate too (this is what the batch loop
    # consumes right after fantasizing a pending/picked member)
    incremental2 = CholeskyPosterior(raw, x, y, capacity=x.shape[0] + 1)
    incremental2.set_pool(xq)
    incremental2.append(dup_x, dup_y)
    fresh.set_pool(xq)
    np.testing.assert_allclose(incremental2.pool_ucb(1.8), fresh.pool_ucb(1.8),
                               atol=5e-3, rtol=5e-3)


def test_append_past_capacity_refuses():
    gp, raw, x, y = _fitted_gp(n=5, d=2)
    post = CholeskyPosterior(raw, x, y, capacity=6)
    assert post.capacity == 64  # bucket floor
    post.n = post.capacity  # simulate a full buffer
    with pytest.raises(ValueError, match="capacity"):
        post.append(np.zeros(2), 0.0)


# ---------------------------------------------------------------------------
# retrace regression: bucket padding pins <= 1 compile per kernel
# ---------------------------------------------------------------------------


def _study_with_trials(n):
    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    root.add_float_param("a", 0.0, 1.0, scale_type=ScaleType.LINEAR)
    root.add_float_param("b", 0.0, 1.0, scale_type=ScaleType.LINEAR)
    root.add_float_param("c", 0.0, 1.0, scale_type=ScaleType.LINEAR)
    cfg.metrics.add("y", "MAXIMIZE")
    cfg.algorithm = "GP_UCB"
    ds = InMemoryDatastore()
    study = Study(name=f"owners/o/studies/retrace-{n}", study_config=cfg)
    ds.create_study(study)
    rng = np.random.RandomState(7)
    for _ in range(n):
        a, b, c = rng.rand(3)
        t = Trial(parameters={"a": a, "b": b, "c": c})
        t.complete(Measurement(
            metrics={"y": -(a - 0.4) ** 2 - (b - 0.6) ** 2 - c * 0.1}))
        ds.create_trial(study.name, t)
    return cfg, ds, study


def test_engine_kernels_do_not_retrace_across_20_varying_ops():
    """20 suggest ops at 20 different trial counts (and mixed batch counts)
    inside one shape bucket: every engine kernel compiles at most once.
    Before the engine, each distinct (n_trials, pool_size) retraced the
    jitted acquisition."""
    # warm the jit caches at the bucket the loop will use, then count
    cfg, ds, study = _study_with_trials(33)
    supporter = DatastorePolicySupporter(ds, study.name)
    policy = GPBanditPolicy(supporter, n_candidates=120, min_completed=4,
                            warm_start=False)
    req = SuggestRequest(
        study_descriptor=StudyDescriptor(config=cfg, guid=study.name), count=1)
    policy.suggest(req)

    reset_trace_counts()
    rng = np.random.RandomState(3)
    for op in range(20):  # trial counts 34..53, counts alternate 1/8
        a, b, c = rng.rand(3)
        t = Trial(parameters={"a": a, "b": b, "c": c})
        t.complete(Measurement(metrics={"y": -(a - 0.4) ** 2}))
        ds.create_trial(study.name, t)
        req = SuggestRequest(
            study_descriptor=StudyDescriptor(config=cfg, guid=study.name),
            count=1 if op % 2 else 8)
        decision = policy.suggest(req)
        assert len(decision.suggestions) == (1 if op % 2 else 8)
    assert all(v <= 1 for v in TRACE_COUNTS.values()), dict(TRACE_COUNTS)


def test_trace_counters_tick_on_fresh_shapes():
    """Sanity for the counter itself: a never-seen bucket does retrace (the
    regression test above is not vacuously green)."""
    rng = np.random.RandomState(0)
    d = 7  # dimension unused anywhere else in the suite
    raw = _raw_tree(d, rng)
    reset_trace_counts()
    post = CholeskyPosterior(raw, rng.rand(10, d), rng.randn(10))
    post.set_pool(rng.rand(30, d))
    assert TRACE_COUNTS["factor"] == 1
    assert TRACE_COUNTS["attach_pool"] == 1


# ---------------------------------------------------------------------------
# engine == pre-engine path, trial for trial (acceptance)
# ---------------------------------------------------------------------------


def _suggest(policy, cfg, study, count):
    return policy.suggest(SuggestRequest(
        study_descriptor=StudyDescriptor(config=cfg, guid=study.name),
        count=count)).suggestions


@pytest.mark.parametrize("count,with_pending", [(1, False), (8, False),
                                                (4, True)])
def test_engine_agrees_with_pre_engine_path_trial_for_trial(count,
                                                            with_pending):
    cfg, ds, study = _study_with_trials(14)
    if with_pending:
        rng = np.random.RandomState(11)
        for _ in range(2):
            a, b, c = rng.rand(3)
            t = Trial(parameters={"a": a, "b": b, "c": c})
            t.state = TrialState.ACTIVE
            ds.create_trial(study.name, t)
    supporter = DatastorePolicySupporter(ds, study.name)
    # warm_start off: both paths must run the identical deterministic fit
    engine = GPBanditPolicy(supporter, n_candidates=300, min_completed=4,
                            warm_start=False, use_engine=True)
    legacy = GPBanditPolicy(supporter, n_candidates=300, min_completed=4,
                            warm_start=False, use_engine=False)
    got = _suggest(engine, cfg, study, count)
    want = _suggest(legacy, cfg, study, count)
    assert len(got) == len(want) == count
    for s_eng, s_leg in zip(got, want):
        assert s_eng.parameters.as_dict() == s_leg.parameters.as_dict()
