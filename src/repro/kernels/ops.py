"""jit'd dispatch wrappers over the Pallas kernels.

Default behavior (``impl="auto"``):
  * on TPU backends -> Pallas kernel path
  * on every other backend -> XLA reference path
Tests select a path explicitly with ``impl=`` ("pallas_interpret" runs the
Pallas kernels on the CPU).
"""

from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref

Impl = Literal["auto", "xla", "pallas", "pallas_interpret"]


def _default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# Candidate pools at or above this row count go through the blocked path:
# K(x1, x2) is built in (n, GRAM_BLOCK_ROWS) column strips, bounding the
# per-call workspace (Pallas grid / XLA temp) instead of materializing one
# n x m product for arbitrarily large acquisition batches.
GRAM_BLOCK_ROWS = 4096

# XLA matvec strip width over x1's rows: bounds the temporary cross-Gram to
# (MATVEC_BLOCK_ROWS, m) — the Pallas path needs no strips at all.
MATVEC_BLOCK_ROWS = 256


def matern52_gram(
    x1: jnp.ndarray,
    x2: jnp.ndarray,
    amplitude=1.0,
    *,
    impl: Impl = "auto",
    block_rows: Optional[int] = None,
) -> jnp.ndarray:
    """Matérn-5/2 Gram matrix of lengthscale-scaled features.

    ``block_rows``: strip width over x2's rows. None = auto (blocked once
    x2 has >= GRAM_BLOCK_ROWS rows); 0 = never block.
    """
    impl = _default_impl() if impl == "auto" else impl
    m = x2.shape[0]
    if block_rows is None:
        block_rows = GRAM_BLOCK_ROWS if m >= GRAM_BLOCK_ROWS else 0
    if block_rows and m > block_rows:
        # Every strip is computed at the full block width: the final partial
        # strip is zero-padded up to ``block_rows`` and its result columns
        # sliced back off. A ragged tail would hand the jitted kernels a
        # distinct x2 shape per distinct pool size — one fresh compile per
        # tail shape, breaking the retrace-free serving invariant.
        strips = []
        for i in range(0, m, block_rows):
            strip = x2[i:i + block_rows]
            w = strip.shape[0]
            if w < block_rows:
                strip = jnp.pad(strip, ((0, block_rows - w), (0, 0)))
            out = matern52_gram(x1, strip, amplitude, impl=impl, block_rows=0)
            strips.append(out[:, :w] if w < block_rows else out)
        return jnp.concatenate(strips, axis=1)
    if impl == "xla":
        return ref.matern52_gram(x1, x2, amplitude)
    return _gram_pallas(x1, x2, jnp.asarray(amplitude, jnp.float32),
                        impl == "pallas_interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gram_pallas(x1, x2, amplitude, interpret):
    """The Pallas Gram with a VJP: the GP fit differentiates through it."""
    from repro.kernels.gram import matern52_gram_pallas

    return matern52_gram_pallas(x1, x2, amplitude, interpret=interpret)


def _gram_pallas_fwd(x1, x2, amplitude, interpret):
    return _gram_pallas(x1, x2, amplitude, interpret), (x1, x2, amplitude)


def _gram_pallas_bwd(interpret, residuals, g):
    # XLA-only piece: the backward pass is the VJP of the jnp reference, so
    # the kernel path's gradients are exactly the reference path's, and its
    # contractions run at HIGHEST as the kernel's forward does.
    _, vjp = jax.vjp(ref.matern52_gram, *residuals)
    return vjp(g)


_gram_pallas.defvjp(_gram_pallas_fwd, _gram_pallas_bwd)


def matern52_gram_matvec(
    x1: jnp.ndarray,
    x2: jnp.ndarray,
    alpha: jnp.ndarray,
    amplitude=1.0,
    *,
    impl: Impl = "auto",
    block_rows: Optional[int] = None,
) -> jnp.ndarray:
    """Fused posterior-mean contraction K(x1, x2)^T · alpha -> (m,).

    Never materializes the (n, m) cross-Gram: the Pallas kernel accumulates
    tile-by-tile on TPU; the XLA path folds x1 row-strips into the output so
    the peak temporary is (block_rows, m) instead of (n, m).

    ``block_rows``: strip width over x1's rows on the XLA path. None = auto
    (strips of MATVEC_BLOCK_ROWS once x1 has more rows than that); 0 = one
    unblocked contraction.
    """
    impl = _default_impl() if impl == "auto" else impl
    if impl != "xla":
        from repro.kernels.gram import matern52_gram_matvec_pallas

        return matern52_gram_matvec_pallas(
            x1, x2, alpha, jnp.asarray(amplitude),
            interpret=(impl == "pallas_interpret"))
    n = x1.shape[0]
    if block_rows is None:
        block_rows = MATVEC_BLOCK_ROWS
    if not block_rows or n <= block_rows:
        return ref.matern52_gram_matvec(x1, x2, alpha, amplitude)
    alpha = alpha.astype(jnp.float32)
    pad = (-n) % block_rows
    x1p = jnp.pad(x1.astype(jnp.float32), ((0, pad), (0, 0)))
    ap = jnp.pad(alpha, (0, pad))  # zero alpha rows contribute nothing
    strips = n // block_rows + (1 if pad else 0)

    def fold(acc, strip):
        xs, als = strip
        return acc + ref.matern52_gram_matvec(xs, x2, als, amplitude), None

    acc0 = jnp.zeros((x2.shape[0],), jnp.float32)
    out, _ = jax.lax.scan(
        fold, acc0,
        (x1p.reshape(strips, block_rows, x1.shape[1]),
         ap.reshape(strips, block_rows)))
    return out


def tri_solve(
    L: jnp.ndarray,
    b: jnp.ndarray,
    *,
    trans: bool = False,
    impl: Impl = "auto",
) -> jnp.ndarray:
    """x with L x = b (``trans``: L^T x = b); L (m, m) lower-triangular.

    ``b`` may be (m,) or (m, k); the result matches b's shape. The Pallas
    path runs the blocked forward-substitution kernel; transposed solves go
    through the flip trick (reverse both axes of L, transpose, reverse b's
    rows) so the SAME compiled kernel serves both orientations — no second
    kernel, no extra compile.
    """
    impl = _default_impl() if impl == "auto" else impl
    if impl == "xla":
        return ref.tri_solve(L, b, trans=trans)
    from repro.kernels.tri_solve import tri_solve_pallas

    interpret = impl == "pallas_interpret"
    vec = b.ndim == 1
    bm = b[:, None] if vec else b
    if trans:
        out = tri_solve_pallas(L[::-1, ::-1].T, bm[::-1],
                               interpret=interpret)[::-1]
    else:
        out = tri_solve_pallas(L, bm, interpret=interpret)
    return out[:, 0] if vec else out


def cholupdate(
    L: jnp.ndarray,
    v: jnp.ndarray,
    *,
    impl: Impl = "auto",
) -> jnp.ndarray:
    """chol(L L^T + v v^T) in O(m^2): the sparse posterior's rank-1 append
    against the m×m inducing factor. L (m, m) lower-triangular, v (m,)."""
    impl = _default_impl() if impl == "auto" else impl
    if impl == "xla":
        return ref.cholupdate(L, v)
    from repro.kernels.tri_solve import cholupdate_pallas

    return cholupdate_pallas(L, v, interpret=(impl == "pallas_interpret"))


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset: int = 0,
    impl: Impl = "auto",
) -> jnp.ndarray:
    """Attention dispatch: Pallas flash kernel on TPU, chunked-XLA otherwise."""
    impl = _default_impl() if impl == "auto" else impl
    if impl == "xla":
        from repro.models.attention import chunked_attention

        return chunked_attention(q, k, v, causal=causal, q_offset=q_offset)
    from repro.kernels.flash_attention import flash_attention_pallas

    return flash_attention_pallas(
        q, k, v, causal=causal, q_offset=q_offset,
        interpret=(impl == "pallas_interpret"),
    )


def ssd_scan(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    Bm: jnp.ndarray,
    Cm: jnp.ndarray,
    *,
    init_state: Optional[jnp.ndarray] = None,
    chunk: int = 256,
    impl: Impl = "auto",
):
    """Mamba2 SSD scan dispatch (chunked parallel form)."""
    impl = _default_impl() if impl == "auto" else impl
    if impl == "xla":
        from repro.models.mamba2 import ssd_chunked

        return ssd_chunked(x, dt, A, Bm, Cm, init_state=init_state, chunk=chunk)
    from repro.kernels.mamba2_ssd import ssd_scan_pallas

    return ssd_scan_pallas(
        x, dt, A, Bm, Cm, init_state=init_state, chunk=chunk,
        interpret=(impl == "pallas_interpret"),
    )
