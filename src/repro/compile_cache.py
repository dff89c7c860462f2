"""JAX's persistent compilation cache, for the repository's entry points.

``chip_smoke.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache` first thing, so a second process on the same
checkout loads its compiled programs instead of compiling them again.
Importing ``repro`` never turns the cache on, and the tests leave it off.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR``, when it is set — JAX reads the variable
  itself, and no other directory is set here;
* otherwise :data:`CHECKOUT_CACHE_DIR`, ``<checkout>/.jax_cache``.  The
  path is fixed because it is part of the cache key: a directory that
  moved per run would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
