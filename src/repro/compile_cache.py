"""JAX's persistent compilation cache, kept at one fixed place.

Entry points that compile for a chip (``chip_smoke.py``, the serving
example and server, the benchmark harness) call :func:`enable` once before
their first compile. Library code and tests never call it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because the directory is part of what a later process must
find again (a temporary or per-process directory would never hit).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
