#!/usr/bin/env python3
"""Records the small chip trace that ``bench/tests/test_trace_reduce.py`` reads.

    python3 bench/tools/record_trace.py <out.xplane.pb>

On one TPU chip: the three Pallas kernels the cells run, at served shapes
(Gram 512 x 2560 at 30 features, triangular solve 256 x 2560, rank-1 update
of a 256 x 256 factor), each called three times inside a profiler trace,
with a ``bench.policy`` host span around the second round.
"""

import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    rng = np.random.RandomState(0)
    x1 = jnp.asarray(rng.rand(512, 30), jnp.float32)
    x2 = jnp.asarray(rng.rand(2560, 30), jnp.float32)
    a = rng.randn(256, 256)
    L = jnp.asarray(np.linalg.cholesky(a @ a.T / 256 + np.eye(256)), jnp.float32)
    b = jnp.asarray(rng.randn(256, 2560), jnp.float32)
    v = jnp.asarray(rng.randn(256), jnp.float32)
    gram = jax.jit(lambda p, q: ops.matern52_gram(p, q, 1.3))
    solve = jax.jit(lambda m, r: ops.tri_solve(m, r))
    update = jax.jit(lambda m, u: ops.cholupdate(m, u))
    for f, args in ((gram, (x1, x2)), (solve, (L, b)), (update, (L, v))):
        f(*args).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for r in range(3):
        span = jax.profiler.TraceAnnotation("bench.policy") if r == 1 else None
        if span:
            span.__enter__()
        for f, args in ((gram, (x1, x2)), (solve, (L, b)), (update, (L, v))):
            f(*args).block_until_ready()
        if span:
            span.__exit__(None, None, None)
    jax.profiler.stop_trace()
    from bench.trace_reduce import find_xplane

    out = sys.argv[1]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(find_xplane(tmp), out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(out, os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
