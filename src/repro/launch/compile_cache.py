"""Persistent XLA compilation cache for the entry-point scripts.

Every process on a fresh machine compiles the engine and dozens of small
synthesis programs again, so every entry point (``chip_smoke.py``, ``repro.launch.simulate``,
``benchmarks.run``) calls :func:`enable_compile_cache` once before it
touches JAX. Library imports and tests never do.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no directory is
  set here, so the cache lives there and nowhere else.
- unset: the cache goes to ``<checkout>/.jax_cache`` (git-ignored). The
  path is fixed — not derived from a temporary name, a pid or the time —
  because it is part of what makes a later process hit the cache.

Either way the cache key covers each program's metadata. JAX's default key
leaves it out, and an executable loaded from the cache keeps the metadata
it was compiled with: a wave loop that differs from a cached one only in
its ``jax.named_scope``s would load the cached names, and a profile of it
would name the wrong stages, or none. Source paths in the metadata are
taken relative to the checkout, so a checkout elsewhere still hits.
"""
from __future__ import annotations

import os
import re

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", ".."))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory, keyed on
    the programs' metadata too, and return that directory."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT + os.sep))
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
