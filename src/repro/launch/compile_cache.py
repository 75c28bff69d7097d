"""Persistent XLA compile cache for the command-line entry points.

Call :func:`enable_compile_cache` from a ``main()`` (never at import time,
never from the tests): where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's
own setting governs and nothing is changed here; otherwise compiled
programs are kept under ``<checkout>/.jax_cache``, a fixed path (the path
is part of the cache key, so a directory named after a temp dir, a pid or
the time would never hit).
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
