#!/usr/bin/env python3
"""Records the small chip trace with program spans that
``bench/tests/test_idle_split.py`` reads.

    python3 bench/tools/record_span_trace.py <out.xplane.pb>

On one TPU chip, inside a profiler trace: the three Pallas kernels the
cells run, at the shapes of ``record_trace.py``, in three rounds of one
call each, with ``repro.tracing`` spans around them as the served path
opens them. Between the rounds the device idles for 3 ms each way the
idle split tells apart: inside a working span (``vizier.datastore.decode``
under ``vizier.worker.batch``), inside a waiting span only
(``vizier.op.wait``), and inside no span.
"""

import os
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

IDLE_S = 0.003


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import tracing
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        print("record_span_trace: needs a TPU", file=sys.stderr)
        return 2
    rng = np.random.RandomState(0)
    x1 = jnp.asarray(rng.rand(512, 30), jnp.float32)
    x2 = jnp.asarray(rng.rand(2560, 30), jnp.float32)
    a = rng.randn(256, 256)
    L = jnp.asarray(np.linalg.cholesky(a @ a.T / 256 + np.eye(256)),
                    jnp.float32)
    b = jnp.asarray(rng.randn(256, 2560), jnp.float32)
    v = jnp.asarray(rng.randn(256), jnp.float32)
    gram = jax.jit(lambda p, q: ops.matern52_gram(p, q, 1.3))
    solve = jax.jit(lambda m, r: ops.tri_solve(m, r))
    update = jax.jit(lambda m, u: ops.cholupdate(m, u))
    calls = ((gram, (x1, x2)), (solve, (L, b)), (update, (L, v)))

    def kernels():
        for f, args in calls:
            f(*args).block_until_ready()

    kernels()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    op = "owners/trace/studies/s/operations/0"
    with tracing.span("vizier.worker.batch", trace_id=(op,), ops=1):
        with tracing.span("vizier.policy.suggest", count=1):
            kernels()
        with tracing.span("vizier.datastore.decode", trials=0):
            time.sleep(IDLE_S)
        with tracing.span("vizier.policy.acquire"):
            kernels()
    with tracing.span("vizier.op.wait", trace_id=op):
        time.sleep(IDLE_S)
    time.sleep(IDLE_S)
    kernels()
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
