"""Placement of JAX's persistent compilation cache.

A chip run compiles every serving program from scratch unless a cache
from an earlier process is found.  The cache's directory is part of what
makes an entry findable again, so it is never a temporary, per-process or
per-run name: either the deployment names it through
``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself), or it is the fixed
``.jax_cache/`` at the checkout root (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/launch/``).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call before the process compiles anything.  With
    ``JAX_COMPILATION_CACHE_DIR`` set nothing is configured here — JAX
    already reads the variable — otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
