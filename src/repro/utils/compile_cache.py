"""Where JAX's persistent compilation cache lives for the entry points.

A cold chip run compiles every program (the Monte-Carlo engine alone takes
tens of seconds per certification horizon), so the launchers keep compiled
programs across runs.  The cache key includes the directory, so the path
is fixed: never a temporary directory, a pid or a time.
"""

from __future__ import annotations

import os
import pathlib

# <checkout>/.jax_cache (listed in .gitignore)
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to
    :data:`CACHE_DIR`.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
