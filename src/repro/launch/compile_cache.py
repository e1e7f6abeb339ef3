"""JAX's persistent compilation cache, placed from outside or at a fixed
path in the checkout.

The cache directory is part of what makes an entry findable again, so it
never moves between runs: ``JAX_COMPILATION_CACHE_DIR`` when it is set
(JAX reads the variable itself and nothing here overrides it), otherwise
``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every program, however quickly it
    compiled (a server's decode step compiles in under JAX's default
    one-second floor); returns the directory the cache uses."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
