"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` before their first compile.
``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
nothing is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``
(git-ignored): a fixed path, because the path is part of what a later run
must match to find an earlier run's programs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the compile-cache directory this process uses, pointing JAX
    at ``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is
    set."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
