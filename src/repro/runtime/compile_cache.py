"""JAX's persistent compile cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/``) call
:func:`use_compile_cache` before their first compile; the library never
does, at import or anywhere else, and neither do the tests.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing. Otherwise the cache goes to ``<checkout>/.jax_cache`` — a
fixed path, since the directory is part of what a later run must find
(a path built from a temp name, a pid or a time never hits again).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; return its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
