"""JAX's persistent compilation cache for the entry points.

The packed evaluator takes minutes to compile for a TPU, so scripts turn
the cache on before their first compile by calling
:func:`enable_compile_cache`.  Importing the package never does.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

# fixed, inside the checkout: the path is part of the cache key, so a
# directory that moves between runs would never hit
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and nothing is changed; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
