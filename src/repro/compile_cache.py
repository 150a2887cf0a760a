"""JAX's persistent compilation cache, kept at one fixed directory.

Entry points (``chip_smoke.py``, the examples, the benchmarks) call
``enable()`` before their first compile, so a second run of the same program
on the same machine loads its compiled kernels instead of compiling again.
The directory is part of the cache key, so it never comes from a temp name,
a pid or the time: ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else
``.jax_cache`` at the root of the checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
import pathlib

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """The directory the cache uses: the environment's, else the checkout's."""
    return os.environ.get(CACHE_DIR_ENV) or str(DEFAULT_CACHE_DIR)


def enable() -> str:
    """Points JAX's persistent compilation cache at ``cache_dir()``.

    Every program is cached, however quickly it compiled: a suggest op runs
    a few dozen small programs, and together they are most of a cold op.
    """
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
