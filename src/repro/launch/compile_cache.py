"""Where JAX's persistent compilation cache lives for this repo's entry
points (``chip_smoke.py``, ``benchmarks.run``).

Called by entry points only, never at import time: a library that moved the
cache behind its importer's back would surprise every caller.
"""
from __future__ import annotations

import os
from pathlib import Path

# Fixed, inside the checkout (and listed in .gitignore): the cache is only
# found again when a later process looks in the same place.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is where it lives: JAX reads
    the variable itself, so nothing else is set here.  Otherwise it lives
    at ``<checkout>/.jax_cache``."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
