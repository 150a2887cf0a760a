"""The GP fit's gradient through the Pallas Gram's custom VJP.

The fit (``gp_bandit._neg_mll``) differentiates through ``ops._gram_pallas``,
whose backward is the VJP of the jnp reference Gram. On a TPU a float32
contraction at the default precision runs in one bf16 pass, which put the
fit's log_amp gradient off by up to 1.6, so the backward's contractions
must carry HIGHEST as the kernel's forward does. On the CPU every
contraction is full float32 whatever it carries, so the first test reads
the precision off the lowered backward, and the second checks the values
against a float64 gradient of the plain formula.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.pythia import gp_bandit


def _points(n, d, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(0.0, 1.0, (n, d)), jnp.float32)


def test_gram_backward_contracts_at_highest():
    x1, x2 = _points(24, 5, 0), _points(40, 5, 1)
    amp = jnp.float32(1.3)
    _, vjp = jax.vjp(lambda a, b, c: ops._gram_pallas(a, b, c, True),
                     x1, x2, amp)
    text = jax.jit(vjp).lower(jnp.ones((24, 40), jnp.float32)).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert dots, text
    for ln in dots:
        assert re.search(r"precision\s*=\s*\[HIGHEST,\s*HIGHEST\]", ln), ln


def _plain_neg_mll(raw, x, y):
    """The fit's objective from its formula, in float64: Matern-5/2 ARD
    Gram from pairwise differences, Cholesky, the log-normal priors."""
    amp = jnp.exp(raw["log_amp"])
    ell = jnp.exp(raw["log_ell"])
    noise = jnp.exp(raw["log_noise"]) + 1e-4
    xs = x / ell
    d2 = jnp.sum((xs[:, None, :] - xs[None, :, :]) ** 2, axis=-1)
    a = jnp.sqrt(5.0 * d2 + 1e-30)
    K = amp * (1.0 + a + a * a / 3.0) * jnp.exp(-a)
    K = K + noise * jnp.eye(x.shape[0])
    L = jnp.linalg.cholesky(K)
    w = jax.scipy.linalg.solve_triangular(L, y, lower=True)
    mll = (-0.5 * jnp.dot(w, w) - jnp.sum(jnp.log(jnp.diagonal(L)))
           - 0.5 * x.shape[0] * jnp.log(2.0 * jnp.pi))
    prior = (-0.5 * raw["log_amp"] ** 2
             - 0.5 * jnp.sum((raw["log_ell"] - jnp.log(0.3)) ** 2)
             - 0.5 * (raw["log_noise"] - jnp.log(1e-2)) ** 2 / 4.0)
    return -(mll + prior)


@pytest.fixture
def pallas_interpret(monkeypatch):
    """``impl="auto"`` resolves to the Pallas kernels in interpret mode;
    traces cached under either dispatch are dropped on the way in and out."""
    monkeypatch.setattr(ops, "_default_impl", lambda: "pallas_interpret")
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("seed", [0, 1])
def test_fit_gradient_through_the_pallas_gram_matches_float64(
        pallas_interpret, seed):
    n, rows, d = 20, 32, 4           # 20 trials in a bucket of 32 rows
    rng = np.random.default_rng(seed)
    x = np.zeros((rows, d), np.float32)
    y = np.zeros((rows,), np.float32)
    mask = np.zeros((rows,), np.float32)
    x[:n] = rng.uniform(0.0, 1.0, (n, d))
    y[:n] = np.sin(3.0 * x[:n, 0]) + 0.5 * x[:n, 1] - 0.2
    y[:n] = (y[:n] - y[:n].mean()) / y[:n].std()
    mask[:n] = 1.0
    raw = {"log_amp": np.float32(0.3 * seed - 0.2),
           "log_ell": np.log(np.linspace(0.3, 0.8, d)).astype(np.float32),
           "log_noise": np.float32(np.log(5e-3))}

    got = jax.grad(gp_bandit._neg_mll)(
        {k: jnp.asarray(v) for k, v in raw.items()}, jnp.asarray(x),
        jnp.asarray(y), jnp.asarray(mask))
    with jax.enable_x64(True):
        want = jax.grad(_plain_neg_mll)(
            {k: jnp.asarray(v, jnp.float64) for k, v in raw.items()},
            jnp.asarray(x[:n], jnp.float64), jnp.asarray(y[:n], jnp.float64))
        want = {k: np.asarray(v) for k, v in want.items()}
    for leaf in ("log_amp", "log_noise"):
        np.testing.assert_allclose(float(got[leaf]), float(want[leaf]),
                                   rtol=1e-4, atol=1e-6, err_msg=leaf)
