"""Operations and least bytes of one Matern-5/2 Gram kernel call.

The call as launched (``repro/kernels/gram.py``): x1 (N, D), x2 (M, D) and
the amplitude in, K (N, M) out, f32, with N, M and D the padded sizes the
kernel is given (D padded to the 128 lanes, so the padding's products are
counted as the work the MXU does). Operations: the cross term's 2 N M D
multiply-adds, the two row norms, and 10 elementwise operations per output
(combine, sqrt, the polynomial, exp, scale). Least bytes: each input read
once and the output written once.
"""

NAMES = ("matern52_gram_pallas",)


def cost(operands, result):
    (n, d), (m, _d2) = operands[0], operands[1]
    flops = 2.0 * n * m * d + 2.0 * (n + m) * d + 10.0 * n * m
    nbytes = 4.0 * (n * d + m * d + 1 + n * m)
    return flops, nbytes
