"""JAX's persistent compilation cache for the repo's entry points.

A run on the chip compiles every engine program from scratch unless the
compiled executables survive in a persistent cache; the cache key includes
the directory, so a cache that moves never hits. Entry points (scripts,
examples, ``chip_smoke.py``) call :func:`enable_compile_cache` before
their first compile. Importing the library never does.

``python -c "from repro.compile_cache import enable_compile_cache as e; print(e())"``
prints where the cache lives.
"""
from __future__ import annotations

import os
import pathlib

#: the checkout this package runs from (``<checkout>/src/repro/``)
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing. Otherwise the cache is ``<checkout>/.jax_cache`` — a
    fixed path, so the next run of any entry point finds it again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
