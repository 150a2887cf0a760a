"""SGPR inducing-point posterior (repro.pythia.sparse_posterior).

Pins the tentpole's acceptance criteria: with Z = X the sparse posterior is
exact (matches CholeskyPosterior to ~jitter), a chain of rank-1 appends
against the m×m inducing factor equals a fresh factorization with the same
sites, pool rescoring after appends matches a fresh attach, the policy
switches dense -> sparse strictly above SPARSE_THRESHOLD, and every sparse
engine kernel compiles at most once across shape-stable operations.
"""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import Measurement, StudyConfig, Trial
from repro.core.study import Study
from repro.pythia import gp_bandit as gpb
from repro.pythia.gp_bandit import GPBanditPolicy, StackedResidualGP
from repro.pythia.policy import StudyDescriptor, SuggestRequest
from repro.pythia.posterior import (
    CholeskyPosterior,
    TRACE_COUNTS,
    reset_trace_counts,
)
from repro.pythia.sparse_posterior import (
    N_INDUCING,
    SPARSE_THRESHOLD,
    SparsePosterior,
    inducing_sites,
)
from repro.pythia.supporter import DatastorePolicySupporter
from repro.service.datastore import InMemoryDatastore


def _raw_tree(d, rng):
    return {
        "log_amp": np.float32(rng.uniform(-0.5, 0.5)),
        "log_ell": np.full((d,), np.log(0.4) + rng.uniform(-0.2, 0.2),
                           np.float32),
        "log_noise": np.float32(rng.uniform(-5.0, -3.0)),
    }


def _design(n, d, seed):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, d).astype(np.float32)
    y = (np.sin(3 * x[:, 0]) + 0.5 * x[:, -1]
         + 0.05 * rng.randn(n)).astype(np.float32)
    return rng, x, y


# ---------------------------------------------------------------------------
# exactness: Z = X makes SGPR the dense posterior (up to jitter)
# ---------------------------------------------------------------------------


def test_sparse_with_z_equal_x_matches_dense():
    rng, x, y = _design(60, 3, 0)
    raw = _raw_tree(3, rng)
    dense = CholeskyPosterior(raw, x, y)
    sparse = SparsePosterior(raw, x, y, z=x)
    xq = rng.rand(40, 3).astype(np.float32)
    md, sd = dense.query(xq)
    ms, ss = sparse.query(xq)
    np.testing.assert_allclose(ms, md, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(ss, sd, atol=1e-3, rtol=1e-3)

    dense.set_pool(xq)
    sparse.set_pool(xq)
    np.testing.assert_allclose(sparse.pool_ucb(1.8), dense.pool_ucb(1.8),
                               atol=2e-3, rtol=2e-3)


def test_mean_is_kernel_matvec_against_inducing_sites():
    """alpha is the inducing-weight vector: K(q, Z) @ alpha must equal the
    posterior mean — the contract the stacked-mean kernels rely on."""
    from repro.kernels import ops as kops

    rng, x, y = _design(200, 3, 1)
    raw = _raw_tree(3, rng)
    post = SparsePosterior(raw, x, y, n_inducing=64, seed=0)
    xq = rng.rand(30, 3).astype(np.float32)
    mean, _ = post.query(xq)
    import jax.numpy as jnp
    ell = np.exp(np.asarray(raw["log_ell"], np.float64))
    amp = float(np.exp(raw["log_amp"]))
    via_matvec = np.asarray(kops.matern52_gram_matvec(
        jnp.asarray(post.inducing_z / ell, jnp.float32),
        jnp.asarray(xq / ell, jnp.float32),
        post.alpha, amp, impl="xla"))
    np.testing.assert_allclose(via_matvec, mean, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# rank-1 appends == fresh factorization with the same sites
# ---------------------------------------------------------------------------


@given(st.integers(min_value=30, max_value=80),
       st.integers(min_value=1, max_value=5),
       st.integers(min_value=0, max_value=10**6))
@settings(max_examples=10, deadline=None)
def test_append_equals_refit_property(n, k, seed):
    rng = np.random.RandomState(seed)
    d = 3
    raw = _raw_tree(d, rng)
    x = rng.rand(n, d).astype(np.float32)
    y = rng.randn(n).astype(np.float32)
    adds_x = rng.rand(k, d).astype(np.float32)
    adds_y = rng.randn(k).astype(np.float32)
    z = inducing_sites(32, d, seed=7)

    incremental = SparsePosterior(raw, x, y, z=z, capacity=n + k)
    for ax, ay in zip(adds_x, adds_y):
        incremental.append(ax, ay)
    fresh = SparsePosterior(raw, np.vstack([x, adds_x]),
                            np.concatenate([y, adds_y]), z=z)
    xq = rng.rand(20, d).astype(np.float32)
    m_inc, s_inc = incremental.query(xq)
    m_new, s_new = fresh.query(xq)
    # tolerance scales with 1/noise: the whitened update vector u = Luu^-1
    # k(Z, x*)/sigma grows as sigma shrinks, so f32 accumulation in the
    # cholupdate/Sherman-Morrison chain leaves ~5e-3 worst-case drift at the
    # smallest fitted noise this property draws (~7e-3)
    np.testing.assert_allclose(m_inc, m_new, atol=1e-2, rtol=1e-2)
    np.testing.assert_allclose(s_inc, s_new, atol=1e-2, rtol=1e-2)


def test_pool_rescore_after_append_matches_fresh_attach():
    rng, x, y = _design(120, 3, 3)
    raw = _raw_tree(3, rng)
    z = inducing_sites(48, 3, seed=0)
    pool = rng.rand(90, 3).astype(np.float32)

    post = SparsePosterior(raw, x, y, z=z, capacity=x.shape[0] + 2)
    post.set_pool(pool)
    xa = rng.rand(3).astype(np.float32)
    post.append(xa, 0.7)

    fresh = SparsePosterior(raw, np.vstack([x, xa[None]]),
                            np.concatenate([y, [0.7]]), z=z)
    fresh.set_pool(pool)
    np.testing.assert_allclose(post.pool_mean(), fresh.pool_mean(),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(post.pool_std(), fresh.pool_std(),
                               atol=2e-3, rtol=2e-3)


def test_append_pool_member_matches_manual_append_at_cached_mean():
    rng, x, y = _design(100, 3, 4)
    raw = _raw_tree(3, rng)
    pool = rng.rand(70, 3).astype(np.float32)
    a = SparsePosterior(raw, x, y, n_inducing=48, seed=0,
                        capacity=x.shape[0] + 1)
    b = SparsePosterior(raw, x, y, n_inducing=48, seed=0,
                        capacity=x.shape[0] + 1)
    for p in (a, b):
        p.set_pool(pool)
    i = int(np.argmax(a.pool_ucb(1.8)))
    a.append_pool_member(i)
    b.append(pool[i], float(b.pool_mean()[i]))
    np.testing.assert_allclose(a.pool_mean(), b.pool_mean(),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(a.pool_std(), b.pool_std(),
                               atol=1e-4, rtol=1e-4)


def test_append_past_capacity_refuses():
    rng, x, y = _design(30, 2, 5)
    raw = _raw_tree(2, rng)
    post = SparsePosterior(raw, x, y, n_inducing=16, seed=0, capacity=30)
    post.n = post.capacity  # simulate a full design buffer
    with pytest.raises(ValueError, match="capacity"):
        post.append(np.zeros(2, np.float32), 0.0)


# ---------------------------------------------------------------------------
# inducing sites: deterministic per (m, d, seed)
# ---------------------------------------------------------------------------


def test_inducing_sites_deterministic_and_in_unit_cube():
    z1 = inducing_sites(64, 5, seed=3)
    z2 = inducing_sites(64, 5, seed=3)
    np.testing.assert_array_equal(z1, z2)
    assert z1.shape == (64, 5)
    assert (z1 >= 0).all() and (z1 <= 1).all()
    assert not np.array_equal(z1, inducing_sites(64, 5, seed=4))


# ---------------------------------------------------------------------------
# retrace pins: every sparse kernel compiles at most once per shape bucket
# ---------------------------------------------------------------------------


def test_sparse_kernels_do_not_retrace_across_shape_stable_ops():
    rng, x, y = _design(300, 3, 6)
    raw = _raw_tree(3, rng)
    pool = rng.rand(150, 3).astype(np.float32)

    # warm every kernel at the bucket the loop will use
    warm = SparsePosterior(raw, x, y, n_inducing=64, seed=0,
                           capacity=x.shape[0] + 4)
    warm.set_pool(pool)
    warm.append_pool_member(0)
    warm.append(pool[1], 0.1)
    warm.query(pool[:20])

    reset_trace_counts()
    for op in range(3):  # varying n inside one train bucket
        n = 300 + op * 7
        xo = rng.rand(n, 3).astype(np.float32)
        yo = rng.randn(n).astype(np.float32)
        post = SparsePosterior(raw, xo, yo, n_inducing=64, seed=0,
                               capacity=n + 4)
        post.set_pool(pool)
        post.append_pool_member(op)
        post.append(pool[op + 3], 0.2)
        post.query(pool[:20])
    sparse_counts = {k: v for k, v in TRACE_COUNTS.items()
                     if k.startswith("sparse_")}
    # empty == zero retraces (the warm pass populated every jit cache);
    # the tick test below keeps this from being vacuously green
    assert all(v <= 1 for v in sparse_counts.values()), sparse_counts


def test_sparse_trace_counters_tick_on_fresh_shapes():
    """Sanity: the pin above is not vacuously green."""
    rng, x, y = _design(90, 6, 7)  # dimension unused elsewhere in the suite
    raw = _raw_tree(6, rng)
    reset_trace_counts()
    post = SparsePosterior(raw, x, y, n_inducing=16, seed=0)
    post.set_pool(rng.rand(30, 6).astype(np.float32))
    assert TRACE_COUNTS["sparse_factor"] == 1
    assert TRACE_COUNTS["sparse_attach_pool"] == 1


# ---------------------------------------------------------------------------
# policy switch: dense at/below the threshold, sparse strictly above
# ---------------------------------------------------------------------------


def _study_with_trials(n, name):
    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    root.add_float_param("a", 0.0, 1.0)
    root.add_float_param("b", 0.0, 1.0)
    cfg.metrics.add("y", "MAXIMIZE")
    cfg.algorithm = "GP_UCB"
    ds = InMemoryDatastore()
    study = Study(name=f"owners/o/studies/{name}", study_config=cfg)
    ds.create_study(study)
    rng = np.random.RandomState(13)
    for _ in range(n):
        a, b = rng.rand(2)
        t = Trial(parameters={"a": a, "b": b})
        t.complete(Measurement(metrics={"y": -(a - 0.3) ** 2 - (b - 0.7) ** 2}))
        ds.create_trial(study.name, t)
    return cfg, ds, study


def _suggest(policy, cfg, study, count=1):
    return policy.suggest(SuggestRequest(
        study_descriptor=StudyDescriptor(config=cfg, guid=study.name),
        count=count)).suggestions


def test_policy_stays_dense_at_or_below_threshold(monkeypatch):
    monkeypatch.setattr(gpb, "SPARSE_THRESHOLD", 40)
    cfg, ds, study = _study_with_trials(40, "dense-at-threshold")
    policy = GPBanditPolicy(DatastorePolicySupporter(ds, study.name),
                            n_candidates=100, min_completed=4,
                            warm_start=False)
    sugg = _suggest(policy, cfg, study)
    assert len(sugg) == 1
    assert policy.last_sparse is False


def test_policy_goes_sparse_above_threshold(monkeypatch):
    monkeypatch.setattr(gpb, "SPARSE_THRESHOLD", 40)
    cfg, ds, study = _study_with_trials(41, "sparse-above-threshold")
    policy = GPBanditPolicy(DatastorePolicySupporter(ds, study.name),
                            n_candidates=100, min_completed=4,
                            warm_start=False)
    sugg = _suggest(policy, cfg, study, count=3)
    assert len(sugg) == 3
    assert policy.last_sparse is True
    for s in sugg:
        p = s.parameters.as_dict()
        assert 0.0 <= p["a"] <= 1.0 and 0.0 <= p["b"] <= 1.0
    # batch members are distinct points (fantasized appends steer away)
    pts = {tuple(sorted(s.parameters.as_dict().items())) for s in sugg}
    assert len(pts) == 3


def test_sparse_level_feeds_stacked_mean_via_inducing_basis(monkeypatch):
    """A sparse level's contribution to the stack mean goes through the
    (Z, alpha_u) basis — finite values, agreeing with the level's query."""
    rng, x, y = _design(SPARSE_THRESHOLD + 50, 3, 8)
    stack = StackedResidualGP(dim=3, seed=0)
    stack.fit_level(x, y, capacity=x.shape[0] + 2)
    lvl = stack.levels[-1]
    assert isinstance(lvl.posterior, SparsePosterior)
    assert lvl.mean_x.shape == (N_INDUCING, 3)
    xq = rng.rand(12, 3).astype(np.float32)
    via_stack = stack.mean(xq)
    via_query, _ = lvl.posterior.query(xq)
    np.testing.assert_allclose(via_stack, via_query, atol=1e-4, rtol=1e-4)
