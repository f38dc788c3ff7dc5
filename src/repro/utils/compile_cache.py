"""Where JAX keeps its persistent compilation cache.

Entry points that compile large programs (``chip_smoke.py``, the
benchmark drivers) call :func:`enable_compile_cache` before their first
compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it
itself and nothing is set here.  Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` — a fixed path, because the path is part of what
the cache is found by; a temporary or per-process directory never hits.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
