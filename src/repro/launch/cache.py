"""JAX persistent compilation cache for the repository's entry points.

Entry points (``launch/train.py``, ``chip_smoke.py``, the benchmark
``main`` functions) call :func:`enable_compile_cache` once at start-up;
nothing calls it at import.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout root (``src/repro/launch/cache.py`` → three levels up)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache`` —
    a fixed path, since the directory is part of what a later process must
    find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
