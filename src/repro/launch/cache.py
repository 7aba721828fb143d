"""Where JAX's persistent compilation cache lives.

Entry points call :func:`use_compile_cache` before their first jitted
call.  The directory is part of each cache entry's key, so it must not
move between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself, so nothing is set in code),
otherwise ``.jax_cache/`` at the root of this checkout.
"""
from __future__ import annotations

import os

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
