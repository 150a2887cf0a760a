"""Triangular-solve + rank-1 Cholesky-update kernels vs jax.scipy oracles.

Covers the sparse-posterior kernel stack end to end: the Pallas blocked
forward-substitution kernel (both orientations through the ops.py flip
trick), the O(m^2) column-sweep cholupdate against a fresh-factorization
oracle, padding neutrality (lane/block padding must never change values),
and compile-count pins for the jitted entry points.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.tri_solve import (
    BLOCK_K,
    LANE,
    cholupdate_pallas,
    tri_solve_pallas,
)

RNG = np.random.RandomState(17)


def _chol_factor(m, seed=0):
    """A well-conditioned random lower-triangular factor."""
    rng = np.random.RandomState(seed)
    A = rng.randn(m, m).astype(np.float32)
    K = A @ A.T + m * np.eye(m, dtype=np.float32)
    return np.linalg.cholesky(K).astype(np.float32)


# ---------------------------------------------------------------------------
# tri-solve vs the jax.scipy oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k", [(5, 3), (8, 1), (64, 64), (130, 7),
                                 (128, 256), (256, 300)])
@pytest.mark.parametrize("trans", [False, True])
def test_tri_solve_sweep(m, k, trans):
    L = _chol_factor(m, seed=m + k)
    b = RNG.randn(m, k).astype(np.float32)
    want = np.asarray(ref.tri_solve(jnp.asarray(L), jnp.asarray(b),
                                    trans=trans))
    got = np.asarray(ops.tri_solve(jnp.asarray(L), jnp.asarray(b),
                                   trans=trans, impl="pallas_interpret"))
    assert got.shape == (m, k)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_tri_solve_vector_rhs():
    """(m,) right-hand sides round-trip through the (m, 1) kernel shape."""
    L = _chol_factor(40, seed=2)
    b = RNG.randn(40).astype(np.float32)
    for trans in (False, True):
        want = np.asarray(ref.tri_solve(jnp.asarray(L), jnp.asarray(b),
                                        trans=trans))
        got = np.asarray(ops.tri_solve(jnp.asarray(L), jnp.asarray(b),
                                       trans=trans, impl="pallas_interpret"))
        assert got.shape == (40,)
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@given(st.integers(min_value=2, max_value=40),
       st.integers(min_value=1, max_value=20),
       st.integers(min_value=0, max_value=10**6))
@settings(max_examples=15, deadline=None)
def test_tri_solve_solves_system_property(m, k, seed):
    """Property: L @ x reproduces b (checked against the system, not just
    another solver) for random sizes and factors."""
    L = _chol_factor(m, seed=seed)
    b = np.random.RandomState(seed + 1).randn(m, k).astype(np.float32)
    x = np.asarray(ops.tri_solve(jnp.asarray(L), jnp.asarray(b),
                                 impl="pallas_interpret"))
    np.testing.assert_allclose(L @ x, b, atol=5e-4, rtol=5e-4)
    xt = np.asarray(ops.tri_solve(jnp.asarray(L), jnp.asarray(b),
                                  trans=True, impl="pallas_interpret"))
    np.testing.assert_allclose(L.T @ xt, b, atol=5e-4, rtol=5e-4)


def test_tri_solve_padding_neutrality():
    """m exactly at / just past the LANE boundary and k at / past BLOCK_K:
    padding must be value-neutral, not just shape-correct."""
    for m in (LANE - 1, LANE, LANE + 1):
        for k in (BLOCK_K - 1, BLOCK_K, BLOCK_K + 1):
            L = _chol_factor(m, seed=m)
            b = np.random.RandomState(k).randn(m, k).astype(np.float32)
            want = np.asarray(ref.tri_solve(jnp.asarray(L), jnp.asarray(b)))
            got = np.asarray(tri_solve_pallas(jnp.asarray(L), jnp.asarray(b),
                                              interpret=True))
            np.testing.assert_allclose(got, want, atol=5e-4, rtol=5e-4)


# ---------------------------------------------------------------------------
# cholupdate vs the fresh-factorization oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [3, 8, 64, 130, 256])
def test_cholupdate_sweep(m):
    L = _chol_factor(m, seed=m)
    v = RNG.randn(m).astype(np.float32)
    oracle = np.linalg.cholesky(
        L @ L.T + np.outer(v, v) + 1e-6 * np.eye(m)).astype(np.float32)
    got = np.asarray(ops.cholupdate(jnp.asarray(L), jnp.asarray(v),
                                    impl="pallas_interpret"))
    np.testing.assert_allclose(got, oracle, atol=2e-3, rtol=2e-3)
    # result is lower-triangular with positive diagonal
    np.testing.assert_allclose(got, np.tril(got), atol=1e-6)
    assert (np.diag(got) > 0).all()


@given(st.integers(min_value=2, max_value=48),
       st.integers(min_value=0, max_value=10**6))
@settings(max_examples=15, deadline=None)
def test_cholupdate_reconstructs_updated_gram_property(m, seed):
    """Property: got @ got.T == L L^T + v v^T for random sizes/updates."""
    L = _chol_factor(m, seed=seed)
    v = np.random.RandomState(seed + 3).randn(m).astype(np.float32)
    got = np.asarray(ops.cholupdate(jnp.asarray(L), jnp.asarray(v),
                                    impl="pallas_interpret"))
    np.testing.assert_allclose(got @ got.T, L @ L.T + np.outer(v, v),
                               atol=5e-3, rtol=5e-3)


def test_cholupdate_xla_matches_pallas():
    """The two dispatch rungs agree (the XLA scan is the CPU default)."""
    L = _chol_factor(33, seed=5)
    v = RNG.randn(33).astype(np.float32)
    xla = np.asarray(ops.cholupdate(jnp.asarray(L), jnp.asarray(v),
                                    impl="xla"))
    pal = np.asarray(ops.cholupdate(jnp.asarray(L), jnp.asarray(v),
                                    impl="pallas_interpret"))
    np.testing.assert_allclose(xla, pal, atol=1e-4, rtol=1e-4)


def test_cholupdate_padding_neutrality():
    for m in (LANE - 1, LANE, LANE + 1):
        L = _chol_factor(m, seed=m)
        v = np.random.RandomState(m).randn(m).astype(np.float32)
        want = np.asarray(ref.cholupdate(jnp.asarray(L), jnp.asarray(v)))
        got = np.asarray(cholupdate_pallas(jnp.asarray(L), jnp.asarray(v),
                                           interpret=True))
        np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# compile-count pins: one compile per kernel across shape-stable callers
# ---------------------------------------------------------------------------


def test_tri_solve_single_compile_across_flip_orientations():
    """Forward and transposed solves share ONE compiled kernel (the flip
    trick feeds the transposed case through the same (m, k) signature)."""
    m, k = 64, 32
    L = jnp.asarray(_chol_factor(m, seed=9))
    b = jnp.asarray(RNG.randn(m, k).astype(np.float32))
    before = tri_solve_pallas._cache_size()
    ops.tri_solve(L, b, impl="pallas_interpret")
    ops.tri_solve(L, b, trans=True, impl="pallas_interpret")
    ops.tri_solve(L, b + 1.0, impl="pallas_interpret")
    assert tri_solve_pallas._cache_size() - before <= 1


def test_cholupdate_single_compile_across_repeat_updates():
    m = 96
    L = jnp.asarray(_chol_factor(m, seed=4))
    before = cholupdate_pallas._cache_size()
    out = L
    for i in range(3):
        v = jnp.asarray(np.random.RandomState(i).randn(m).astype(np.float32))
        out = ops.cholupdate(out, v, impl="pallas_interpret")
    assert cholupdate_pallas._cache_size() - before <= 1
