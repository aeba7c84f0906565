"""JAX's persistent compilation cache, at one fixed place per checkout.

Every entry point (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``, the examples) calls :func:`enable_compile_cache` before
its first compile. A 32-layer round compiles for a minute or more; with
the cache a second run in the same checkout loads it instead.

The directory is part of the cache key, so it is a fixed path: no pid,
time or tempdir in it.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` (listed in .gitignore)
CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set here. Otherwise the cache lives at
    :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
