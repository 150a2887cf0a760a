"""Kernel micro-benchmarks: CPU-interpret sanity timings + analytic FLOPs.

Wall times here are interpret-mode (Python) — meaningless as TPU perf; the
derived column carries the analytic FLOP counts the roofline uses.
"""

import numpy as np
import jax.numpy as jnp

from benchmarks.bench_util import emit, timeit

from repro import compile_cache
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gram import matern52_gram_pallas
from repro.kernels.mamba2_ssd import ssd_scan_pallas

RNG = np.random.RandomState(0)


def main() -> None:
    compile_cache.enable()
    # gram
    n, m, d = 256, 256, 32
    x1 = jnp.asarray(RNG.randn(n, d), jnp.float32)
    x2 = jnp.asarray(RNG.randn(m, d), jnp.float32)
    amp = jnp.asarray(1.0)
    us = timeit(lambda: matern52_gram_pallas(x1, x2, amp, interpret=True
                                             ).block_until_ready(), repeats=3)
    emit("kernel.gram.256x256x32", us, f"flops={2*n*m*d:.3e}")
    us = timeit(lambda: ref.matern52_gram(x1, x2, 1.0).block_until_ready(),
                repeats=3)
    emit("kernel.gram.ref_xla", us, "")

    # flash attention
    B, S, H, D = 1, 128, 4, 64
    q = jnp.asarray(RNG.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(RNG.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(RNG.randn(B, S, H, D), jnp.float32)
    us = timeit(lambda: flash_attention_pallas(q, k, v, bq=64, bk=64,
                                               interpret=True
                                               ).block_until_ready(), repeats=3)
    emit("kernel.flash.B1S128H4D64", us, f"flops={4*B*H*S*S*D:.3e}")
    us = timeit(lambda: ref.attention(q, k, v).block_until_ready(), repeats=3)
    emit("kernel.flash.ref_xla", us, "")

    # ssd
    B, S, Hh, P, G, N = 1, 256, 4, 32, 2, 32
    x = jnp.asarray(RNG.randn(B, S, Hh, P), jnp.float32)
    dt = jnp.asarray(RNG.rand(B, S, Hh) * 0.3 + 0.01, jnp.float32)
    A = jnp.asarray(-np.abs(RNG.rand(Hh)) - 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.randn(B, S, G, N) * 0.3, jnp.float32)
    Cm = jnp.asarray(RNG.randn(B, S, G, N) * 0.3, jnp.float32)
    us = timeit(lambda: ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=64,
                                        interpret=True)[0].block_until_ready(),
                repeats=3)
    chunk = 64
    flops = B * Hh * (S // chunk) * (2 * chunk * chunk * N + 2 * chunk * chunk * P
                                     + 4 * chunk * P * N)
    emit("kernel.ssd.B1S256H4P32", us, f"flops={flops:.3e}")


if __name__ == "__main__":
    main()
