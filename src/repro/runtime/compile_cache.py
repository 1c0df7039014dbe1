"""Where JAX keeps its persistent compilation cache.

A cache entry is keyed by the program and by the directory's path, so a
directory that moves between runs never hits.  ``JAX_COMPILATION_CACHE_DIR``
places it from outside (JAX reads the variable itself, and nothing here
overrides it); otherwise it lives at ``<checkout>/.jax_cache``, a fixed,
git-ignored path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (call
    before the first compile) and return that directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
