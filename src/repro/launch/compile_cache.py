"""Where JAX keeps its persistent compilation cache.

A cache entry is keyed on the directory it lives in, so the directory must
not move between runs.  An operator places it with
``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable itself, and nothing
more is set here); otherwise it is the fixed ``<checkout>/.jax_cache``.
Entry points call :func:`use_compile_cache` from their ``main``; importing
this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ=os.environ) -> tuple[str, bool]:
    """(cache directory, whether the environment chose it)."""
    if environ.get(ENV_VAR):
        return environ[ENV_VAR], True
    return str(CHECKOUT_CACHE), False


def use_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir`; returns
    the directory."""
    path, from_env = compile_cache_dir()
    if not from_env:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
