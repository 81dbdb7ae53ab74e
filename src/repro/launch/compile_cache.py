"""Placement of JAX's persistent compilation cache for the entry points.

Entry points (``python -m repro.launch.discord``, ``chip_smoke.py``,
the ``benchmarks/`` CLIs) call :func:`use_compile_cache` once at start;
the library itself and the tests never touch the cache.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
  here overrides it.
* Otherwise the cache lives in ``<checkout>/.jax_cache`` — a fixed
  path, since the path is part of what makes a later run find the
  entries again.  Git ignores the directory.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the checkout root: src/repro/launch/compile_cache.py -> 3 levels up
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; return its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
