"""The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to the checkout's fixed ``.jax_cache``."""
import os

import jax

from repro.runtime import compile_cache


def _restore(prev):
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_wins(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    try:
        assert compile_cache.use_compile_cache() == str(tmp_path)
        # the code sets no path of its own: JAX keeps the one it had
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        _restore(prev)


def test_default_dir_is_the_checkout(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        got = compile_cache.use_compile_cache()
        assert got == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.use_compile_cache() == got  # fixed, not per run
    finally:
        _restore(prev)
