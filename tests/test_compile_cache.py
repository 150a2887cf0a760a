"""The persistent compilation cache lives at one fixed directory."""

import os

import jax

from repro import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_dir_is_the_environment_setting(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_cache_dir_defaults_to_the_ignored_checkout_directory(monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_enable_points_jax_at_the_cache_dir(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
