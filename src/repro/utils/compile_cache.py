"""Where JAX keeps its persistent compilation cache.

Entry points that compile whole models (``chip_smoke.py``,
``repro.launch.serve``, ``repro.launch.train``) call
:func:`enable_compile_cache` once, before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
here.  Otherwise the cache goes to one fixed, git-ignored directory inside
the checkout: the directory is part of each entry's key, so a path that moved
between runs (a temp name, a pid, a time) would never hit.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
