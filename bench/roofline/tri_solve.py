"""Operations and least bytes of one triangular-solve kernel call.

The call as launched (``repro/kernels/tri_solve.py``): L (M, M) lower
triangular and B (M, K) in, X (M, K) out, f32, padded sizes. Operations:
forward substitution's M^2 K (M (M + 1) / 2 multiply-adds per column, the
divisions included). Least bytes: L and B read once, X written once.
"""

NAMES = ("tri_solve_pallas",)


def cost(operands, result):
    (m, _m2), (_m3, k) = operands[0], operands[1]
    flops = float(m) * m * k
    nbytes = 4.0 * (m * m + 2 * m * k)
    return flops, nbytes
