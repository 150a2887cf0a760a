"""Compile the GP-bandit kernels and the served programs for a TPU v5e.

Nothing runs: each program is compiled for one chip of a described (not
attached) v5e:2x2 topology, which catches what interpret mode cannot — a
kernel the TPU lowering refuses, or a program that is not differentiable
through a kernel. Shapes are the served ones: train bucket 512, pool bucket
2560, 30 features, a 256 x 256 inducing factor.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest workers import every
test file. The kernel dispatch is steered to Pallas inside the tests, since
``impl="auto"`` sees this process's CPU backend.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.pythia import gp_bandit, posterior, sparse_posterior

N, M_POOL, M, D = 512, 2560, 256, 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32,
                                              sharding=one_chip)


@pytest.fixture
def pallas_dispatch(monkeypatch):
    """``impl="auto"`` resolves to the Pallas kernels for this test; traces
    cached under either dispatch are dropped on the way in and out."""
    monkeypatch.setattr(ops, "_default_impl", lambda: "pallas")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _assert_kernel_compiled(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _raw(shape, *lead):
    return {"log_amp": shape(*lead), "log_ell": shape(*lead, D),
            "log_noise": shape(*lead)}


KERNELS = {
    "gram": (lambda s: (s(N, D), s(M_POOL, D), s()),
             lambda a, b, amp: ops.matern52_gram(a, b, amp, impl="pallas")),
    "gram_matvec": (lambda s: (s(N, D), s(M_POOL, D), s(N), s()),
                    lambda a, b, al, amp: ops.matern52_gram_matvec(
                        a, b, al, amp, impl="pallas")),
    "tri_solve": (lambda s: (s(M, M), s(M, M_POOL)),
                  lambda L, b: ops.tri_solve(L, b, impl="pallas")),
    "tri_solve_trans": (lambda s: (s(M, M), s(M, M_POOL)),
                        lambda L, b: ops.tri_solve(L, b, trans=True,
                                                   impl="pallas")),
    "cholupdate": (lambda s: (s(M, M), s(M)),
                   lambda L, v: ops.cholupdate(L, v, impl="pallas")),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(shape, name):
    args, fn = KERNELS[name]
    _assert_kernel_compiled(fn, *args(shape))


def test_fit_step_compiles_for_v5e(shape, pallas_dispatch):
    """The Adam step differentiates through the Pallas Gram (custom VJP)."""
    raw = _raw(shape)
    _assert_kernel_compiled(
        gp_bandit._fit_step, raw, raw, raw, shape(N, D), shape(N), shape(N),
        shape(), shape(), shape())


def test_fit_step_metrics_compiles_for_v5e(shape, pallas_dispatch):
    """The vmapped k=2 multi-metric step, Pallas Gram in its forward pass."""
    raw = _raw(shape, 2)
    _assert_kernel_compiled(
        gp_bandit._fit_step_metrics, raw, raw, raw, shape(N, D),
        shape(2, N), shape(N), shape(), shape(), shape())


ENGINE = {
    "dense_factor": lambda s: (posterior._factor,
                               (_raw(s), s(N, D), s(N), s(N))),
    "dense_attach_pool": lambda s: (
        posterior._attach_pool,
        (_raw(s), s(N, D), s(N), s(N, N), s(N), s(M_POOL, D))),
    "sparse_factor": lambda s: (
        sparse_posterior._sfactor,
        (_raw(s), s(M, D), s(8192, D), s(8192), s(8192))),
    "sparse_attach_pool": lambda s: (
        sparse_posterior._sattach_pool,
        (_raw(s), s(M, D), s(M, M), s(M, M), s(M), s(M_POOL, D))),
    "sparse_append_member": lambda s: (
        sparse_posterior._sappend_member,
        (_raw(s), s(M, D), s(M, M), s(M, M), s(M, M), s(M), s(M, M_POOL),
         s(M_POOL), s(M_POOL), s(M_POOL, D),
         jax.ShapeDtypeStruct((), jnp.int32, sharding=s().sharding))),
}


@pytest.mark.parametrize("name", sorted(ENGINE))
def test_engine_program_compiles_for_v5e(shape, pallas_dispatch, name):
    fn, args = ENGINE[name](shape)
    _assert_kernel_compiled(fn, *args)
