"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Matérn-5/2 Gram matrix (GP bandit)
# ---------------------------------------------------------------------------


def matern52_gram(x1: jnp.ndarray, x2: jnp.ndarray, amplitude) -> jnp.ndarray:
    """K[i,j] = amp * (1 + a + a^2/3) exp(-a), a = sqrt(5) * ||x1_i - x2_j||.

    Inputs are already lengthscale-scaled: x / ell.
    x1: (n, d), x2: (m, d) -> (n, m), computed in float32.
    """
    x1 = x1.astype(jnp.float32)
    x2 = x2.astype(jnp.float32)
    # The cross term at full float32, as in the Pallas kernel: the TPU
    # default is one bf16 pass, and the Pallas Gram's backward is this
    # function's VJP, so at that precision the GP fit's log_amp gradient was
    # off by up to 1.6 on a v5e.
    d2 = (
        jnp.sum(x1 * x1, axis=1)[:, None]
        - 2.0 * jnp.dot(x1, x2.T, precision=jax.lax.Precision.HIGHEST)
        + jnp.sum(x2 * x2, axis=1)[None, :]
    )
    d2 = jnp.maximum(d2, 0.0)
    a = jnp.sqrt(5.0 * d2)
    return amplitude * (1.0 + a + (a * a) / 3.0) * jnp.exp(-a)


def matern52_gram_matvec(
    x1: jnp.ndarray, x2: jnp.ndarray, alpha: jnp.ndarray, amplitude
) -> jnp.ndarray:
    """out[j] = sum_i K(x1_i, x2_j) * alpha[i] — the GP posterior mean at x2.

    x1: (n, d), x2: (m, d), alpha: (n,) -> (m,). The oracle materializes the
    full cross-Gram; the Pallas kernel (gram.py) and the blocked XLA dispatch
    (ops.py) compute the same contraction tile-by-tile in O(m) memory.
    """
    K = matern52_gram(x1, x2, amplitude)  # (n, m)
    return K.T @ alpha.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Blocked triangular solve + rank-1 Cholesky update (sparse GP posterior)
# ---------------------------------------------------------------------------


def tri_solve(L: jnp.ndarray, b: jnp.ndarray, *, trans: bool = False) -> jnp.ndarray:
    """x with L x = b (or L^T x = b when ``trans``), L lower-triangular.

    L: (m, m), b: (m,) or (m, k) -> same shape as b, computed in float32.
    """
    return jax.scipy.linalg.solve_triangular(
        L.astype(jnp.float32), b.astype(jnp.float32),
        lower=True, trans=1 if trans else 0)


def cholupdate(L: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """chol(L L^T + v v^T) by sequential column rotations: O(m^2).

    L: (m, m) lower-triangular with positive diagonal, v: (m,) -> (m, m).
    Identity-padded trailing rows (diag 1, v 0) pass through untouched, so
    bucket-padded callers stay exact. The test oracle is a fresh
    ``jnp.linalg.cholesky`` of the updated matrix; this column sweep is the
    XLA dispatch path (and the maths the Pallas kernel mirrors).
    """
    L = L.astype(jnp.float32)
    v = v.astype(jnp.float32)
    m = L.shape[0]
    idx = jnp.arange(m)

    def step(carry, k):
        Lc, vc = carry
        col = Lc[:, k]
        Lkk = col[k]
        vk = vc[k]
        r = jnp.sqrt(Lkk * Lkk + vk * vk)
        c = r / Lkk
        s = vk / Lkk
        below = idx > k
        newcol = jnp.where(idx == k, r,
                           jnp.where(below, (col + s * vc) / c, col))
        vc = jnp.where(below, c * vc - s * newcol, vc)
        return (Lc.at[:, k].set(newcol), vc), None

    (L, _), _ = jax.lax.scan(step, (L, v), idx)
    return L


# ---------------------------------------------------------------------------
# Flash attention (causal / non-causal), GQA-aware
# ---------------------------------------------------------------------------


def attention(
    q: jnp.ndarray,  # (B, Sq, Hq, Dh)
    k: jnp.ndarray,  # (B, Sk, Hkv, Dh)
    v: jnp.ndarray,  # (B, Sk, Hkv, Dh)
    *,
    causal: bool = True,
    q_offset: int = 0,
    scale: float | None = None,
) -> jnp.ndarray:
    """Reference full-materialization attention. q_offset positions queries
    within the kv sequence (for decode / chunked prefill)."""
    B, Sq, Hq, Dh = q.shape
    Hkv = k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / (Dh**0.5)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # expand kv heads to match q heads
    kf = jnp.repeat(kf, group, axis=2)
    vf = jnp.repeat(vf, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
    if causal:
        qpos = jnp.arange(Sq)[:, None] + q_offset
        kpos = jnp.arange(k.shape[1])[None, :]
        mask = qpos >= kpos
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space dual) chunked scan
# ---------------------------------------------------------------------------


def ssd_scan(
    x: jnp.ndarray,   # (B, S, H, P)   inputs per head
    dt: jnp.ndarray,  # (B, S, H)      softplus'd step sizes (>0)
    A: jnp.ndarray,   # (H,)           negative decay rates (A < 0)
    Bm: jnp.ndarray,  # (B, S, G, N)   input projection (G groups)
    Cm: jnp.ndarray,  # (B, S, G, N)   output projection
    *,
    init_state: jnp.ndarray | None = None,  # (B, H, P, N)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential reference of the Mamba2 SSD recurrence.

    h_t = exp(A * dt_t) * h_{t-1} + dt_t * x_t B_t^T ;  y_t = h_t C_t
    Returns (y: (B, S, H, P), final_state: (B, H, P, N)).
    """
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert H % G == 0
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2).astype(jnp.float32)  # (B, S, H, N)
    Ch = jnp.repeat(Cm, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)

    h0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((Bsz, H, P, N), jnp.float32)
    )

    def step(h, inputs):
        xt, dtt, bt, ct = inputs  # (B,H,P), (B,H), (B,H,N), (B,H,N)
        decay = jnp.exp(Af[None, :] * dtt)  # (B,H)
        h = h * decay[..., None, None] + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h, ct)
        return h, y

    xs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(Bh, 1, 0),
        jnp.moveaxis(Ch, 1, 0),
    )
    hT, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1)  # (B, S, H, P)
    return y.astype(x.dtype), hT
