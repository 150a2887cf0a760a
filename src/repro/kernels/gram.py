"""Pallas TPU kernels: Matérn-5/2 Gram matrix + fused Gram·vector (GP-bandit
hot-spots).

The GP suggestion path builds K(X, X) ∈ R^{n×n} from lengthscale-scaled
features X ∈ R^{n×d}. On TPU the natural layout is (8,128)-aligned blocks:
each grid cell computes a (BN, BM) tile of K from a (BN, D) and a (BM, D)
VMEM-resident strip, contracting D on the MXU via dot(x1, x2^T).

Tiling: BN = BM = 256 (f32: 256·256·4 = 256 KiB out-tile; two in-strips of
256·D·4; for D ≤ 512 the working set stays ≪ 16 MiB VMEM).

``matern52_gram_matvec_pallas`` fuses the posterior-mean contraction
out = K(x1, x2)^T · alpha into the tile loop: each (BM, BN) grid step folds
its K tile into a (1, BM) accumulator, so the (n, m) cross-Gram is never
materialized in HBM — O(m) output traffic instead of O(n·m). The n-tile grid
axis is innermost, so the output block stays resident across the
accumulation (Pallas revisiting rule).

Inputs are zero-padded to block multiples by the wrapper (ops.py); padding
contributes K values that the wrapper slices away (matvec padding rows carry
alpha = 0, so they contribute exactly nothing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 256
BLOCK_M = 256

# f32 contractions at full precision. The TPU default is one bf16 pass
# (2.3e-3 relative error per dot on a v5e), and d2 = |x1|^2 - 2 x1·x2 +
# |x2|^2 cancels that into the distances: 6.7e-3 relative error in K.
_HIGHEST = jax.lax.Precision.HIGHEST


def _gram_kernel(x1_ref, x2_ref, amp_ref, out_ref):
    """One (BN, BM) tile: d2 = |x1|^2 - 2 x1 x2^T + |x2|^2, then Matérn-5/2."""
    x1 = x1_ref[...].astype(jnp.float32)  # (BN, D)
    x2 = x2_ref[...].astype(jnp.float32)  # (BM, D)
    amp = amp_ref[0, 0]
    # MXU contraction for the cross term; VPU for the norms.
    cross = jax.lax.dot_general(
        x1, x2, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)  # (BN, BM)
    n1 = jnp.sum(x1 * x1, axis=1, keepdims=True)  # (BN, 1)
    n2 = jnp.sum(x2 * x2, axis=1, keepdims=True).T  # (1, BM)
    d2 = jnp.maximum(n1 - 2.0 * cross + n2, 0.0)
    a = jnp.sqrt(5.0 * d2)
    out_ref[...] = amp * (1.0 + a + (a * a) * (1.0 / 3.0)) * jnp.exp(-a)


@functools.partial(jax.jit, static_argnames=("interpret",))
def matern52_gram_pallas(
    x1: jnp.ndarray, x2: jnp.ndarray, amplitude: jnp.ndarray, *, interpret: bool = False
) -> jnp.ndarray:
    """K(x1, x2) with x already scaled by 1/lengthscale. Shapes (n,d),(m,d)."""
    n, d = x1.shape
    m = x2.shape[0]
    pad_n = (-n) % BLOCK_N
    pad_m = (-m) % BLOCK_M
    pad_d = (-d) % 128  # MXU lane alignment
    x1p = jnp.pad(x1.astype(jnp.float32), ((0, pad_n), (0, pad_d)))
    x2p = jnp.pad(x2.astype(jnp.float32), ((0, pad_m), (0, pad_d)))
    amp = jnp.asarray(amplitude, jnp.float32).reshape((1, 1))
    np_, mp_ = n + pad_n, m + pad_m
    dp_ = d + pad_d

    out = pl.pallas_call(
        _gram_kernel,
        grid=(np_ // BLOCK_N, mp_ // BLOCK_M),
        in_specs=[
            pl.BlockSpec((BLOCK_N, dp_), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_M, dp_), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_N, BLOCK_M), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, mp_), jnp.float32),
        interpret=interpret,
    )(x1p, x2p, amp)
    return out[:n, :m]


def _matvec_kernel(x1_ref, x2_ref, alpha_ref, amp_ref, out_ref):
    """One n-tile's contribution to a (1, BM) slice of K^T·alpha.

    Grid is (m_tiles, n_tiles) with n innermost: the out block is revisited
    across the n sweep, zeroed on the first step and accumulated after.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x1 = x1_ref[...].astype(jnp.float32)      # (BN, D)
    x2 = x2_ref[...].astype(jnp.float32)      # (BM, D)
    alpha = alpha_ref[...].astype(jnp.float32)  # (1, BN)
    amp = amp_ref[0, 0]
    cross = jax.lax.dot_general(
        x1, x2, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)  # (BN, BM)
    n1 = jnp.sum(x1 * x1, axis=1, keepdims=True)
    n2 = jnp.sum(x2 * x2, axis=1, keepdims=True).T
    d2 = jnp.maximum(n1 - 2.0 * cross + n2, 0.0)
    a = jnp.sqrt(5.0 * d2)
    k = amp * (1.0 + a + (a * a) * (1.0 / 3.0)) * jnp.exp(-a)  # (BN, BM)
    out_ref[...] += jax.lax.dot_general(
        alpha, k, (((1,), (0,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)  # (1, BM)


@functools.partial(jax.jit, static_argnames=("interpret",))
def matern52_gram_matvec_pallas(
    x1: jnp.ndarray, x2: jnp.ndarray, alpha: jnp.ndarray, amplitude,
    *, interpret: bool = False,
) -> jnp.ndarray:
    """out = K(x1, x2)^T · alpha without materializing the (n, m) cross-Gram.

    x1: (n, d), x2: (m, d), alpha: (n,) -> (m,); x already 1/lengthscale
    scaled. Zero-padded rows of x1 are neutralized by alpha's zero padding.
    """
    n, d = x1.shape
    m = x2.shape[0]
    pad_n = (-n) % BLOCK_N
    pad_m = (-m) % BLOCK_M
    pad_d = (-d) % 128
    x1p = jnp.pad(x1.astype(jnp.float32), ((0, pad_n), (0, pad_d)))
    x2p = jnp.pad(x2.astype(jnp.float32), ((0, pad_m), (0, pad_d)))
    ap = jnp.pad(alpha.astype(jnp.float32), (0, pad_n)).reshape(1, n + pad_n)
    amp = jnp.asarray(amplitude, jnp.float32).reshape((1, 1))
    np_, mp_, dp_ = n + pad_n, m + pad_m, d + pad_d

    out = pl.pallas_call(
        _matvec_kernel,
        grid=(mp_ // BLOCK_M, np_ // BLOCK_N),
        in_specs=[
            pl.BlockSpec((BLOCK_N, dp_), lambda i, j: (j, 0)),
            pl.BlockSpec((BLOCK_M, dp_), lambda i, j: (i, 0)),
            pl.BlockSpec((1, BLOCK_N), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_M), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, mp_), jnp.float32),
        interpret=interpret,
    )(x1p, x2p, ap, amp)
    return out[0, :m]
