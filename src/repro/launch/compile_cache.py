"""Persistent XLA compilation cache for the entry-point scripts.

Every process on a fresh machine compiles the engine and dozens of small
synthesis programs again, so every entry point (``chip_smoke.py``, ``repro.launch.simulate``,
``benchmarks.run``) calls :func:`enable_compile_cache` once before it
touches JAX. Library imports and tests never do.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here, so the cache lives there and nowhere else.
- unset: the cache goes to ``<checkout>/.jax_cache`` (git-ignored). The
  path is fixed — not derived from a temporary name, a pid or the time —
  because it is part of what makes a later process hit the cache.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
