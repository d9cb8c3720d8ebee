"""JAX's persistent compilation cache, placed from outside the library.

Entry points (``chip_smoke.py``, ``repro.launch.*``, ``examples/*``,
``benchmarks/run.py``) call :func:`enable` once at start-up; nothing
calls it at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already keeps its cache there and this sets nothing. Otherwise the cache
goes to ``.jax_cache/`` at the root of the checkout: a fixed path,
because the path is part of what a later run must find again.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
