"""JAX's persistent compilation cache, for the launchers' ``main()``s.

A run on an accelerator can spend minutes compiling; with the cache on,
a second process (or a second ``Engine`` in the same process, whose new
``jax.jit`` objects start with empty in-memory caches) reads the compiled
programs back from disk.

Call :func:`enable_compile_cache` from a ``main()`` before the first
compile — never while a module is imported, so library users and tests
keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``: a path that moved between runs (a temp
    name, a pid, a time) would never be found again."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
