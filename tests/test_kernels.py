"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gram import matern52_gram_matvec_pallas, matern52_gram_pallas
from repro.kernels.mamba2_ssd import ssd_core_pallas, ssd_scan_pallas
from repro.models.mamba2 import ssd_chunked

RNG = np.random.RandomState(42)


# -- gram -----------------------------------------------------------------------


@pytest.mark.parametrize("n,m,d", [(7, 5, 3), (64, 64, 8), (130, 257, 17),
                                   (256, 256, 128), (300, 40, 64)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_gram_sweep(n, m, d, dtype):
    x1 = RNG.randn(n, d).astype(np.float32)
    x2 = RNG.randn(m, d).astype(np.float32)
    K_ref = ref.matern52_gram(jnp.asarray(x1), jnp.asarray(x2), 2.3)
    K_pal = matern52_gram_pallas(jnp.asarray(x1), jnp.asarray(x2),
                                 jnp.asarray(2.3), interpret=True)
    np.testing.assert_allclose(np.asarray(K_ref), np.asarray(K_pal),
                               rtol=1e-4, atol=1e-4)


def test_gram_psd_diagonal():
    x = RNG.randn(20, 4).astype(np.float32)
    K = np.asarray(matern52_gram_pallas(jnp.asarray(x), jnp.asarray(x),
                                        jnp.asarray(1.0), interpret=True))
    np.testing.assert_allclose(np.diag(K), 1.0, atol=1e-5)
    evals = np.linalg.eigvalsh(K + 1e-5 * np.eye(20))
    assert evals.min() > 0


# -- fused gram-matvec (posterior mean without the (n, m) cross-Gram) ----------


@pytest.mark.parametrize("n,m,d", [(5, 7, 2), (64, 64, 8), (300, 257, 17),
                                   (513, 40, 3)])
def test_gram_matvec_sweep(n, m, d):
    x1 = RNG.randn(n, d).astype(np.float32)
    x2 = RNG.randn(m, d).astype(np.float32)
    alpha = RNG.randn(n).astype(np.float32)
    want = np.asarray(ref.matern52_gram_matvec(
        jnp.asarray(x1), jnp.asarray(x2), jnp.asarray(alpha), 1.9))
    got = np.asarray(matern52_gram_matvec_pallas(
        jnp.asarray(x1), jnp.asarray(x2), jnp.asarray(alpha),
        jnp.asarray(1.9), interpret=True))
    assert got.shape == (m,)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_gram_matvec_dispatch_blocked_xla_matches_ref():
    """ops dispatch: the strip-folded XLA path (O(m) peak temporary) equals
    the materializing oracle, and zero-alpha padding rows contribute 0."""
    from repro.kernels import ops as kops

    x1 = jnp.asarray(RNG.randn(700, 5), jnp.float32)
    x2 = jnp.asarray(RNG.randn(123, 5), jnp.float32)
    alpha = jnp.asarray(RNG.randn(700), jnp.float32)
    want = np.asarray(ref.matern52_gram_matvec(x1, x2, alpha, 0.8))
    got = np.asarray(kops.matern52_gram_matvec(x1, x2, alpha, 0.8,
                                               impl="xla", block_rows=256))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # padding neutrality: extra rows with alpha = 0 change nothing
    x1p = jnp.concatenate([x1, jnp.zeros((41, 5), jnp.float32)])
    ap = jnp.concatenate([alpha, jnp.zeros((41,), jnp.float32)])
    got_pad = np.asarray(kops.matern52_gram_matvec(x1p, x2, ap, 0.8,
                                                   impl="xla", block_rows=256))
    np.testing.assert_allclose(got_pad, want, rtol=2e-4, atol=2e-4)


def test_gram_pallas_vjp_matches_reference():
    """The dispatched Pallas Gram is differentiable (custom VJP), and its
    cotangents for x1, x2 and the amplitude are the reference's."""
    from repro.kernels import ops as kops

    x1 = jnp.asarray(RNG.rand(37, 6), jnp.float32)
    x2 = jnp.asarray(RNG.rand(300, 6), jnp.float32)
    w = jnp.asarray(RNG.randn(37, 300), jnp.float32)

    def loss(impl):
        return lambda a, b, amp: jnp.sum(
            w * kops.matern52_gram(a, b, amp, impl=impl))

    args = (x1, x2, jnp.float32(1.4))
    want = jax.grad(loss("xla"), argnums=(0, 1, 2))(*args)
    got = jax.grad(loss("pallas_interpret"), argnums=(0, 1, 2))(*args)
    for g, r in zip(got, want):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


def test_gram_blocked_ragged_tail_compiles_once():
    """Regression: the blocked gram path handed the final partial strip to
    the jitted kernel at its ragged width — one fresh compile per distinct
    tail shape. The strip loop must pad the tail to ``block_rows`` (slicing
    the result back), so every tail size reuses ONE compiled kernel."""
    from repro.kernels import ops as kops

    x1 = jnp.asarray(RNG.randn(6, 4), jnp.float32)
    before = matern52_gram_pallas._cache_size()
    outs = {}
    for m in (13, 21, 29):  # three distinct ragged tails for block_rows=8
        x2 = jnp.asarray(RNG.randn(m, 4), jnp.float32)
        outs[m] = np.asarray(kops.matern52_gram(
            x1, x2, 1.3, impl="pallas_interpret", block_rows=8))
        want = np.asarray(ref.matern52_gram(x1, x2, 1.3))
        np.testing.assert_allclose(outs[m], want, rtol=1e-4, atol=1e-4)
    assert matern52_gram_pallas._cache_size() - before == 1


# -- flash attention ---------------------------------------------------------------


@pytest.mark.parametrize(
    "B,Sq,Sk,Hq,Hkv,D,causal,off",
    [
        (2, 64, 64, 4, 2, 32, True, 0),
        (1, 48, 80, 4, 1, 16, True, 32),     # MQA + decode-style offset
        (2, 32, 32, 2, 2, 64, False, 0),     # bidirectional (whisper encoder)
        (1, 100, 100, 6, 2, 24, True, 0),    # non-power-of-two everything
        (1, 16, 128, 8, 8, 128, True, 112),  # chunked prefill tail
    ])
def test_flash_sweep(B, Sq, Sk, Hq, Hkv, D, causal, off):
    q = jnp.asarray(RNG.randn(B, Sq, Hq, D), jnp.float32)
    k = jnp.asarray(RNG.randn(B, Sk, Hkv, D), jnp.float32)
    v = jnp.asarray(RNG.randn(B, Sk, Hkv, D), jnp.float32)
    o_ref = ref.attention(q, k, v, causal=causal, q_offset=off)
    o_pal = flash_attention_pallas(q, k, v, causal=causal, q_offset=off,
                                   bq=16, bk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_pal),
                               rtol=2e-3, atol=2e-3)


def test_flash_bf16():
    q = jnp.asarray(RNG.randn(1, 32, 2, 32), jnp.bfloat16)
    k = jnp.asarray(RNG.randn(1, 32, 2, 32), jnp.bfloat16)
    v = jnp.asarray(RNG.randn(1, 32, 2, 32), jnp.bfloat16)
    o_ref = ref.attention(q, k, v, causal=True)
    o_pal = flash_attention_pallas(q, k, v, causal=True, bq=16, bk=16,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(o_ref, np.float32),
                               np.asarray(o_pal, np.float32), rtol=0.05, atol=0.05)


def test_chunked_attention_matches_ref_various_chunks():
    from repro.models.attention import chunked_attention

    q = jnp.asarray(RNG.randn(2, 70, 4, 16), jnp.float32)
    k = jnp.asarray(RNG.randn(2, 70, 2, 16), jnp.float32)
    v = jnp.asarray(RNG.randn(2, 70, 2, 16), jnp.float32)
    o_ref = ref.attention(q, k, v, causal=True)
    for qc, kc in [(16, 16), (32, 8), (70, 70), (128, 128)]:
        o = chunked_attention(q, k, v, causal=True, q_chunk=qc, kv_chunk=kc)
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o),
                                   rtol=2e-3, atol=2e-3, err_msg=f"qc={qc} kc={kc}")


# -- SSD -----------------------------------------------------------------------------


@pytest.mark.parametrize("B,S,H,P,G,N,chunk",
                         [(2, 64, 4, 8, 2, 16, 16), (1, 32, 2, 16, 1, 8, 8),
                          (2, 128, 4, 32, 4, 32, 32), (1, 96, 6, 16, 3, 8, 16)])
def test_ssd_kernel_sweep(B, S, H, P, G, N, chunk):
    x = jnp.asarray(RNG.randn(B, S, H, P), jnp.float32)
    dt = jnp.asarray(RNG.rand(B, S, H) * 0.5 + 0.01, jnp.float32)
    A = jnp.asarray(-np.abs(RNG.rand(H)) * 2 - 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.randn(B, S, G, N) * 0.3, jnp.float32)
    Cm = jnp.asarray(RNG.randn(B, S, G, N) * 0.3, jnp.float32)
    h0 = jnp.asarray(RNG.randn(B, H, P, N) * 0.1, jnp.float32)
    y_ref, h_ref = ref.ssd_scan(x, dt, A, Bm, Cm, init_state=h0)
    y_pal, h_pal = ssd_scan_pallas(x, dt, A, Bm, Cm, init_state=h0,
                                   chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(h_ref), np.asarray(h_pal),
                               rtol=2e-3, atol=2e-3)


@given(st.integers(min_value=1, max_value=3), st.sampled_from([16, 32, 48]),
       st.sampled_from([2, 4]), st.sampled_from([8, 16]))
@settings(max_examples=10, deadline=None)
def test_ssd_xla_vs_sequential_property(B, S, H, P):
    """Property: chunked XLA path == sequential scan for random shapes."""
    rng = np.random.RandomState(B * 1000 + S)
    G, N = H // 2 or 1, 8
    x = jnp.asarray(rng.randn(B, S, H, P), jnp.float32)
    dt = jnp.asarray(rng.rand(B, S, H) * 0.3 + 0.01, jnp.float32)
    A = jnp.asarray(-np.abs(rng.rand(H)) - 0.1, jnp.float32)
    Bm = jnp.asarray(rng.randn(B, S, G, N) * 0.3, jnp.float32)
    Cm = jnp.asarray(rng.randn(B, S, G, N) * 0.3, jnp.float32)
    y_ref, h_ref = ref.ssd_scan(x, dt, A, Bm, Cm)
    y_chk, h_chk = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_chk),
                               rtol=2e-3, atol=2e-3)


def test_ssd_kernel_matches_xla_chunked_path():
    """kernels.ops dispatch: pallas-interpret == xla impl == ref."""
    from repro.kernels import ops as kops

    B, S, H, P, G, N = 1, 64, 2, 8, 1, 8
    x = jnp.asarray(RNG.randn(B, S, H, P), jnp.float32)
    dt = jnp.asarray(RNG.rand(B, S, H) * 0.4 + 0.01, jnp.float32)
    A = jnp.asarray(np.array([-0.5, -1.5]), jnp.float32)
    Bm = jnp.asarray(RNG.randn(B, S, G, N) * 0.3, jnp.float32)
    Cm = jnp.asarray(RNG.randn(B, S, G, N) * 0.3, jnp.float32)
    y_x, _ = kops.ssd_scan(x, dt, A, Bm, Cm, impl="xla", chunk=16)
    y_p, _ = kops.ssd_scan(x, dt, A, Bm, Cm, impl="pallas_interpret", chunk=16)
    np.testing.assert_allclose(np.asarray(y_x), np.asarray(y_p),
                               rtol=2e-3, atol=2e-3)
