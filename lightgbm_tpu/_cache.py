"""Persistent XLA compilation cache: the one place that decides where
compiled executables live.

The fused training step takes tens of seconds to compile cold and the
reference's C++ has no such cost, so every process that trains or
serves shares one on-disk cache. ``JAX_COMPILATION_CACHE_DIR`` places
it from outside (jax reads the variable itself; nothing here overrides
it). Unset, the cache is ``.jax_cache/`` at the root of the checkout —
a FIXED path, because the directory is part of the cache key's lookup
and a location that moves (temp name, pid, host tag) never hits.
Tests, benches and worker scripts call ``ensure_compile_cache`` rather
than configuring jax themselves.
"""

from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def ensure_compile_cache() -> str:
    """Idempotent; call before the first jit dispatch. Returns the
    cache directory in use: the one already configured
    (``JAX_COMPILATION_CACHE_DIR`` or the caller's own
    ``jax.config.update``) or else ``CACHE_DIR``. Does not touch the
    backend, so it is safe before ``jax.distributed.initialize``."""
    import jax

    current = jax.config.jax_compilation_cache_dir
    if current:
        return current
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
