"""Pallas TPU kernels: blocked triangular solve + rank-1 Cholesky update.

These are the sparse-posterior (SGPR) hot-spots: every cross-solve against
the m×m inducing factor is a lower-triangular solve with a wide right-hand
side (candidate pools, fantasy batches), and every rank-1 append rotates the
m×m ``LB`` factor with one update vector. Both stay on-device.

``tri_solve_pallas`` solves L X = B for lower-triangular L via blocked
forward substitution: the grid walks BLOCK_K-column strips of B (one strip
per program, L resident in VMEM across the strip); within a strip the row
dimension advances in RB=8-row blocks (the f32 sublane height) — one
(RB, M) × (M, BLOCK_K) MXU contraction (full f32 precision) folds the
already-solved prefix into the block's right-hand side, then the RB×RB
diagonal block is solved with a statically unrolled substitution whose
columns come out of the row block through lane masks: the TPU lowering
accepts dynamic offsets only along rows, in RB-aligned blocks. Transposed
solves (L^T x = b) are handled
by the ops.py wrapper with the flip trick — reverse both axes of L and the
rows of b, solve forward, reverse back — so one kernel serves both.

``cholupdate_pallas`` computes chol(L L^T + v v^T) with the classic column
sweep: for each column k a Givens-style rotation (c, s) derived from the
diagonal and v[k] updates the column and the remainder of v — O(m^2) total,
a single grid-less program with the factor in VMEM. The kernel sweeps the
rows of U = L^T (the wrapper transposes in and out), so column k is read
and written as an RB-aligned row block with a sublane mask.

Padding: wrappers pad m up to a lane-aligned multiple with an IDENTITY
diagonal block (and zero right-hand-side rows / update entries), so padded
solutions are exactly zero and padded columns rotate by the identity —
results are exact, and bucket-padded callers never retrace.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_K = 256   # right-hand-side column strip width
LANE = 128      # m padded to lane multiples (f32 tiling)
RB = 8          # row-block height (f32 sublane)


def _tri_solve_kernel(l_ref, b_ref, out_ref):
    """One BLOCK_K-column strip of X with L X = B, L lower-triangular.

    Every dynamic access is an RB-aligned row block: the diagonal block's
    columns are pulled out of the row block with lane masks, never with a
    slice at a dynamic lane offset (which the TPU lowering refuses).
    """
    M = l_ref.shape[0]
    out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (RB, M), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (RB, 1), 0)

    def row_block(rb, carry):
        start = pl.multiple_of(rb * RB, RB)
        rows = l_ref[pl.ds(start, RB), :]                       # (RB, M)
        # fold the solved prefix: X rows >= start are still zero, so the
        # full-width contraction only picks up columns < start
        S = jax.lax.dot_general(
            rows, out_ref[...], (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)                 # (RB, K)
        rhs = b_ref[pl.ds(start, RB), :] - S
        xblk = jnp.zeros_like(rhs)
        for j in range(RB):  # static unroll: RB sequential pivots
            col = jnp.sum(jnp.where(lane == start + j, rows, 0.0),
                          axis=1, keepdims=True)                # (RB, 1)
            xj = rhs[j:j + 1, :] / col[j:j + 1, :]              # (1, K)
            rhs = rhs - col * xj  # eliminate x_j from the rows below
            xblk = jnp.where(sub == j, xj, xblk)
        out_ref[pl.ds(start, RB), :] = xblk
        return carry

    jax.lax.fori_loop(0, M // RB, row_block, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tri_solve_pallas(
    L: jnp.ndarray, b: jnp.ndarray, *, interpret: bool = False
) -> jnp.ndarray:
    """X with L X = B; L (m, m) lower-triangular, B (m, k) -> (m, k)."""
    m = L.shape[0]
    k = b.shape[1]
    pad_m = (-m) % LANE
    pad_k = (-k) % BLOCK_K
    Lp = jnp.pad(L.astype(jnp.float32), ((0, pad_m), (0, pad_m)))
    if pad_m:
        eye_tail = (jnp.arange(m + pad_m) >= m).astype(jnp.float32)
        Lp = Lp + jnp.diag(eye_tail)  # identity block: padded rows solve to 0
    bp = jnp.pad(b.astype(jnp.float32), ((0, pad_m), (0, pad_k)))
    mp, kp = m + pad_m, k + pad_k

    out = pl.pallas_call(
        _tri_solve_kernel,
        grid=(kp // BLOCK_K,),
        in_specs=[
            pl.BlockSpec((mp, mp), lambda j: (0, 0)),
            pl.BlockSpec((mp, BLOCK_K), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((mp, BLOCK_K), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((mp, kp), jnp.float32),
        interpret=interpret,
    )(Lp, bp)
    return out[:m, :k]


def _cholupdate_kernel(u_ref, v_ref, out_ref):
    """Column sweep of the rank-1 update on U = L^T, in place over out_ref.

    Column k of L is row k of U, so the sweep reads and writes RB-aligned
    row blocks and picks row k out with a sublane mask; v is a (1, M) row.
    """
    M = u_ref.shape[0]
    out_ref[...] = u_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, M), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (RB, M), 0)

    def body(k, v):
        base = pl.multiple_of((k // RB) * RB, RB)
        blk = out_ref[pl.ds(base, RB), :]                       # (RB, M)
        at_row = sub == k - base
        row = jnp.sum(jnp.where(at_row, blk, 0.0), axis=0, keepdims=True)
        at_k = lane == k
        Lkk = jnp.sum(jnp.where(at_k, row, 0.0), axis=1, keepdims=True)
        vk = jnp.sum(jnp.where(at_k, v, 0.0), axis=1, keepdims=True)
        r = jnp.sqrt(Lkk * Lkk + vk * vk)
        c = r / Lkk
        s = vk / Lkk
        below = lane > k
        newrow = jnp.where(at_k, r, jnp.where(below, (row + s * v) / c, row))
        out_ref[pl.ds(base, RB), :] = jnp.where(at_row, newrow, blk)
        return jnp.where(below, c * v - s * newrow, v)

    jax.lax.fori_loop(0, M, body, v_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def cholupdate_pallas(
    L: jnp.ndarray, v: jnp.ndarray, *, interpret: bool = False
) -> jnp.ndarray:
    """chol(L L^T + v v^T); L (m, m) lower-triangular, v (m,) -> (m, m)."""
    m = L.shape[0]
    pad_m = (-m) % LANE
    Lp = jnp.pad(L.astype(jnp.float32), ((0, pad_m), (0, pad_m)))
    if pad_m:
        eye_tail = (jnp.arange(m + pad_m) >= m).astype(jnp.float32)
        Lp = Lp + jnp.diag(eye_tail)  # identity block rotates by identity
    vp = jnp.pad(v.astype(jnp.float32), (0, pad_m)).reshape(1, -1)
    mp = m + pad_m

    out = pl.pallas_call(
        _cholupdate_kernel,
        in_specs=[
            pl.BlockSpec((mp, mp), lambda: (0, 0)),
            pl.BlockSpec((1, mp), lambda: (0, 0)),
        ],
        out_specs=pl.BlockSpec((mp, mp), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, mp), jnp.float32),
        interpret=interpret,
    )(Lp.T, vp)
    return out.T[:m, :m]
