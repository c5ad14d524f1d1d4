"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Every launcher calls ``enable_compile_cache()`` before its first compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is set
here.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed path,
because the path is part of the cache key and a directory that moves never
hits.  Sweep fingerprints that lower to the same HLO then compile once per
cold checkout.
"""
from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
