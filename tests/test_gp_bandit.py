"""GP bandit: posterior sanity + convergence on a smooth objective."""

import math

import numpy as np
import pytest

from repro.core import Measurement, ScaleType, StudyConfig, Trial, TrialState
from repro.pythia.gp_bandit import GPBanditPolicy, GaussianProcessBandit
from repro.pythia.policy import StudyDescriptor, SuggestRequest
from repro.pythia.supporter import DatastorePolicySupporter
from repro.core.study import Study
from repro.service.datastore import InMemoryDatastore


def test_gp_posterior_interpolates():
    gp = GaussianProcessBandit(dim=1, fit_steps=80)
    x = np.linspace(0, 1, 8)[:, None]
    y = np.sin(3 * x[:, 0])
    raw = gp.fit(x, y)
    from repro.pythia.gp_bandit import _posterior
    import jax.numpy as jnp

    mean, std = _posterior(raw, jnp.asarray(x, jnp.float32),
                           jnp.asarray(y, jnp.float32),
                           jnp.asarray(x, jnp.float32))
    assert float(np.max(np.abs(np.asarray(mean) - y))) < 0.3
    xq = np.array([[0.5 / 7 + 0.0001]])
    _, std_q = _posterior(raw, jnp.asarray(x, jnp.float32),
                          jnp.asarray(y, jnp.float32),
                          jnp.asarray(xq, jnp.float32))
    assert float(std_q[0]) < 0.5  # near-data uncertainty is small


def test_vmapped_ucb_matches_per_candidate_reference():
    """Vectorized pool scoring == per-candidate loop oracle within 1e-5."""
    rng = np.random.RandomState(3)
    gp = GaussianProcessBandit(dim=4, fit_steps=40)
    x = rng.rand(15, 4)
    y = np.sin(2 * x.sum(axis=1))
    raw = gp.fit(x, y)
    xq = rng.rand(128, 4)
    vectorized = np.asarray(gp.ucb(raw, x, y, xq))
    reference = gp.ucb_reference(raw, x, y, xq)
    np.testing.assert_allclose(vectorized, reference, atol=1e-5, rtol=1e-5)


def test_blocked_gram_matches_unblocked():
    """Candidate pools >= 4096 rows take the column-strip path, bit-equal."""
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    rng = np.random.RandomState(0)
    x1 = jnp.asarray(rng.rand(23, 6), jnp.float32)
    x2 = jnp.asarray(rng.rand(kops.GRAM_BLOCK_ROWS + 500, 6), jnp.float32)
    unblocked = kops.matern52_gram(x1, x2, 1.7, impl="xla", block_rows=0)
    blocked = kops.matern52_gram(x1, x2, 1.7, impl="xla")  # auto-blocks
    assert blocked.shape == (23, kops.GRAM_BLOCK_ROWS + 500)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(unblocked),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("k", [None, 2])
def test_mll_grad_through_pallas_gram_matches_xla(monkeypatch, k):
    """The fit's gradient through the Pallas Gram (custom VJP) equals the
    gradient through the XLA reference: single-objective, and vmapped over
    k=2 metrics as the multi-metric fit runs it. NaN entries must sit in the
    same places on both paths (the lengthscale gradient is NaN on both: the
    Gram's zero self-distances put sqrt'(0) into it, and ``_fit_step`` zeroes
    it)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    from repro.pythia.gp_bandit import _neg_mll

    rng = np.random.RandomState(5)
    n, bucket, d = 24, 64, 5
    x = np.zeros((bucket, d), np.float32)
    x[:n] = rng.rand(n, d)
    mask = (np.arange(bucket) < n).astype(np.float32)
    lead = () if k is None else (k,)
    y = np.zeros(lead + (bucket,), np.float32)
    y[..., :n] = rng.randn(*lead, n)
    raw = {"log_amp": jnp.full(lead, 0.2, jnp.float32),
           "log_ell": jnp.asarray(np.log(0.4) + 0.1 * rng.randn(*lead, d),
                                  jnp.float32),
           "log_noise": jnp.full(lead, np.log(1e-2), jnp.float32)}
    # the un-jitted body: ``impl="auto"`` is resolved on every trace
    grad = jax.grad(_neg_mll.__wrapped__)
    if k is not None:
        grad = jax.vmap(grad, in_axes=(0, None, 0, None))
    grads = {}
    for impl in ("xla", "pallas_interpret"):
        monkeypatch.setattr(kops, "_default_impl", lambda impl=impl: impl)
        grads[impl] = grad(raw, jnp.asarray(x), jnp.asarray(y),
                           jnp.asarray(mask))
    for key in raw:
        np.testing.assert_allclose(np.asarray(grads["pallas_interpret"][key]),
                                   np.asarray(grads["xla"][key]),
                                   rtol=1e-4, atol=1e-4)
    assert np.all(np.isfinite(np.asarray(grads["pallas_interpret"]["log_amp"])))


def test_fantasized_ucb_vmap_regression():
    """Fantasization: deterministic for a fixed rng, penalizes the pending
    region's uncertainty bonus, and agrees with a per-fantasy loop."""
    import jax.numpy as jnp
    from repro.pythia.gp_bandit import _posterior, _ucb

    rng = np.random.RandomState(7)
    gp = GaussianProcessBandit(dim=2, fit_steps=40)
    x = rng.rand(10, 2)
    y = -((x[:, 0] - 0.5) ** 2) - ((x[:, 1] - 0.5) ** 2)
    raw = gp.fit(x, y)
    pending = np.array([[0.9, 0.9], [0.1, 0.85]])
    xq = rng.rand(64, 2)

    s1 = np.asarray(gp.ucb_fantasized(raw, x, y, pending, xq,
                                      np.random.RandomState(11)))
    s2 = np.asarray(gp.ucb_fantasized(raw, x, y, pending, xq,
                                      np.random.RandomState(11)))
    np.testing.assert_array_equal(s1, s2)  # fixed rng -> fixed fantasies

    # oracle: loop over the same fantasy draws, score one fantasy at a time
    F = 4
    mean_p, std_p = _posterior(raw, jnp.asarray(x, jnp.float32),
                               jnp.asarray(y, jnp.float32),
                               jnp.asarray(pending, jnp.float32))
    eps = np.random.RandomState(11).randn(F, len(pending)).astype(np.float32)
    per_fantasy = []
    for f in range(F):
        y_aug = np.concatenate(
            [y, np.asarray(mean_p) + np.asarray(std_p) * eps[f]])
        x_aug = np.vstack([x, pending])
        per_fantasy.append(np.asarray(
            _ucb(raw, jnp.asarray(x_aug, jnp.float32),
                 jnp.asarray(y_aug, jnp.float32),
                 jnp.asarray(xq, jnp.float32), jnp.float32(gp.ucb_beta))))
    oracle = np.mean(per_fantasy, axis=0)
    np.testing.assert_allclose(s1, oracle, atol=1e-5, rtol=1e-5)

    # regression: conditioning on pending points kills their exploration
    # bonus — candidates at the pending locations score lower than under the
    # pending-blind acquisition
    at_pending = np.asarray(gp.ucb_fantasized(
        raw, x, y, pending, pending, np.random.RandomState(11)))
    blind = np.asarray(gp.ucb(raw, x, y, pending))
    assert (at_pending < blind + 1e-6).all(), (at_pending, blind)


def test_gp_bandit_fantasizes_pending_trials():
    """With a pending trial at the argmax, the next suggestion moves away."""
    cfg = StudyConfig()
    cfg.search_space.select_root().add_float_param("x", 0.0, 1.0)
    cfg.metrics.add("y", "MAXIMIZE")
    cfg.algorithm = "GP_UCB"
    ds = InMemoryDatastore()
    study = Study(name="owners/o/studies/pend", study_config=cfg)
    ds.create_study(study)
    for i in range(8):
        x = (i + 1) / 9.0
        t = Trial(parameters={"x": x})
        t = ds.create_trial(study.name, t)
        t.complete(Measurement(metrics={"y": -(x - 0.55) ** 2}))
        ds.update_trial(study.name, t)

    supporter = DatastorePolicySupporter(ds, study.name)
    policy = GPBanditPolicy(supporter, n_candidates=600, min_completed=4)
    request = SuggestRequest(
        study_descriptor=StudyDescriptor(config=cfg, guid=study.name), count=1)
    (first,) = policy.suggest(request).suggestions
    x_first = first.parameters.get_value("x")

    # park an ACTIVE (pending) trial exactly at the chosen point
    pend = Trial(parameters={"x": x_first})
    pend.state = TrialState.ACTIVE
    ds.create_trial(study.name, pend)

    (second,) = policy.suggest(request).suggestions
    x_second = second.parameters.get_value("x")
    assert abs(x_second - x_first) > 1e-3, (x_first, x_second)


def test_back_to_back_ops_at_fixed_trial_count_differ():
    """Regression: the acquisition RNG was seeded by the completed-trial
    count ALONE, so two suggest operations with no completion in between
    replayed the identical Halton scrambling, local perturbations and
    fantasy draws — the server kept re-suggesting the same point until a
    trial completed. The per-op nonce must break the replay while staying a
    deterministic function of (observed snapshot, op index): a fresh policy
    over the same snapshot still reproduces the first op exactly."""
    cfg = StudyConfig()
    cfg.search_space.select_root().add_float_param("x", 0.0, 1.0)
    cfg.metrics.add("y", "MAXIMIZE")
    cfg.algorithm = "GP_UCB"
    ds = InMemoryDatastore()
    study = Study(name="owners/o/studies/nonce", study_config=cfg)
    ds.create_study(study)
    for i in range(9):
        x = (i + 0.5) / 9.0
        t = Trial(parameters={"x": x})
        t = ds.create_trial(study.name, t)
        t.complete(Measurement(metrics={"y": -(x - 0.42) ** 2}))
        ds.update_trial(study.name, t)

    supporter = DatastorePolicySupporter(ds, study.name)
    request = SuggestRequest(
        study_descriptor=StudyDescriptor(config=cfg, guid=study.name), count=1)

    policy = GPBanditPolicy(supporter, n_candidates=400, min_completed=4,
                            warm_start=False)
    (first,) = policy.suggest(request).suggestions
    (second,) = policy.suggest(request).suggestions  # no completions between
    x1 = first.parameters.get_value("x")
    x2 = second.parameters.get_value("x")
    assert abs(x1 - x2) > 1e-6, (x1, x2)

    # determinism is preserved: a fresh policy over the identical snapshot
    # (op counter 0, same pending set) reproduces the FIRST suggestion
    replay = GPBanditPolicy(supporter, n_candidates=400, min_completed=4,
                            warm_start=False)
    (replayed,) = replay.suggest(request).suggestions
    assert replayed.parameters.get_value("x") == x1


def test_dedup_filter_empty_pool_falls_back_to_unfiltered(monkeypatch):
    """Regression: a pending trial at EVERY candidate used to empty the
    dedup-filtered pool and crash np.argmax on a zero-length array; the
    policy must fall back to the unfiltered pool instead."""
    cfg = StudyConfig()
    cfg.search_space.select_root().add_float_param("x", 0.0, 1.0)
    cfg.metrics.add("y", "MAXIMIZE")
    cfg.algorithm = "GP_UCB"
    ds = InMemoryDatastore()
    study = Study(name="owners/o/studies/dedup", study_config=cfg)
    ds.create_study(study)
    for i in range(8):
        x = (i + 1) / 9.0
        t = Trial(parameters={"x": x})
        t.complete(Measurement(metrics={"y": -(x - 0.55) ** 2}))
        ds.update_trial(study.name, ds.create_trial(study.name, t))

    fixed_pool = np.linspace(0.05, 0.95, 10)[:, None]
    for v in fixed_pool[:, 0]:  # park a pending trial on every candidate
        pend = Trial(parameters={"x": float(v)})
        pend.state = TrialState.ACTIVE
        ds.create_trial(study.name, pend)

    supporter = DatastorePolicySupporter(ds, study.name)
    policy = GPBanditPolicy(supporter, n_candidates=8, min_completed=4)
    monkeypatch.setattr(policy, "_draw_pool",
                        lambda rng, dim, incumbent: fixed_pool.copy())
    request = SuggestRequest(
        study_descriptor=StudyDescriptor(config=cfg, guid=study.name), count=2)
    decision = policy.suggest(request)  # must not raise on the empty filter
    assert len(decision.suggestions) == 2
    for s in decision.suggestions:
        assert 0.0 <= s.parameters.get_value("x") <= 1.0


def test_scrambled_halton_uniformity_and_determinism():
    """The global candidate pool really is quasi-random now: each 1-D
    projection's discrepancy beats iid-uniform sampling by a wide margin,
    and the sequence is deterministic per seed."""
    from repro.pythia.halton import scrambled_halton

    n, dim = 512, 6
    pts = scrambled_halton(n, dim, np.random.RandomState(0))
    assert pts.shape == (n, dim)
    assert (pts >= 0.0).all() and (pts < 1.0).all()
    grid = np.arange(1, n + 1) / n
    for d in range(dim):
        ks = np.abs(np.sort(pts[:, d]) - grid).max()
        assert ks < 0.02, f"dim {d}: KS={ks}"  # iid-uniform is ~0.03-0.06
    # deterministic per seed, fresh scrambling per generator state
    again = scrambled_halton(n, dim, np.random.RandomState(0))
    np.testing.assert_array_equal(pts, again)
    other = scrambled_halton(n, dim, np.random.RandomState(1))
    assert not np.array_equal(pts, other)
    # consecutive draws on one generator differ (per-op rescrambling)
    rng = np.random.RandomState(2)
    a, b = scrambled_halton(64, 2, rng), scrambled_halton(64, 2, rng)
    assert not np.array_equal(a, b)


def test_policy_pool_uses_halton_global_half():
    """The suggest pool's global half is the seeded scrambled-Halton set
    (plus the local-perturbation quarter around the incumbent)."""
    from repro.pythia.halton import scrambled_halton

    supporter = DatastorePolicySupporter(InMemoryDatastore(), "unused")
    policy = GPBanditPolicy(supporter, n_candidates=100)
    rng = np.random.RandomState(5)
    pool = policy._draw_pool(rng, 3, np.array([0.5, 0.5, 0.5]))
    assert pool.shape == (125, 3)
    expect = scrambled_halton(100, 3, np.random.RandomState(5))
    np.testing.assert_array_equal(pool[:100], expect)
    assert (pool >= 0.0).all() and (pool <= 1.0).all()


def test_gp_bandit_converges_1d():
    cfg = StudyConfig()
    cfg.search_space.select_root().add_float_param("x", 0.0, 1.0)
    cfg.metrics.add("y", "MAXIMIZE")
    cfg.algorithm = "GP_UCB"
    ds = InMemoryDatastore()
    study = Study(name="owners/o/studies/gp", study_config=cfg)
    ds.create_study(study)
    supporter = DatastorePolicySupporter(ds, study.name)
    policy = GPBanditPolicy(supporter, n_candidates=400, min_completed=4)

    f = lambda x: -(x - 0.731) ** 2
    best = -1e9
    for i in range(14):
        request = SuggestRequest(
            study_descriptor=StudyDescriptor(config=cfg, guid=study.name), count=1)
        (s,) = policy.suggest(request).suggestions
        x = s.parameters.get_value("x")
        t = Trial(parameters=s.parameters)
        t = ds.create_trial(study.name, t)
        t.complete(Measurement(metrics={"y": f(x)}))
        ds.update_trial(study.name, t)
        best = max(best, f(x))
    assert best > -0.004, f"GP-UCB best={best} (should be near 0)"
