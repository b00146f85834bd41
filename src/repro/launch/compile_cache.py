"""JAX's persistent compilation cache, at a path that can be placed from
outside.  Called by the entry points' ``main()``s, never at import."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``,
    a fixed path, so the next process on the same checkout finds what this
    one compiled; every program is kept there, however quick its compile
    (the serving programs each compile in about a second, and together
    they are a run's start-up).  Must run before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
