"""Operations and least bytes of one rank-1 Cholesky update kernel call.

The call as launched (``repro/kernels/tri_solve.py``): U = L^T (M, M) and
v (1, M) in, the updated factor (M, M) out, f32, padded sizes. Operations:
for column k, the rotation of the M - k entries below it and of v, 6 per
entry, plus the pivot's sqrt and divisions: 3 M^2 in all. Least bytes: the
factor read once and written once, v read once.
"""

NAMES = ("cholupdate_pallas",)


def cost(operands, result):
    m = operands[0][0]
    flops = 3.0 * m * m
    nbytes = 4.0 * (2 * m * m + m)
    return flops, nbytes
