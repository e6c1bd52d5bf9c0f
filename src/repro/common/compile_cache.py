"""Where JAX's persistent compilation cache lives.

The cache key includes the cache path, so a directory that moves never
hits: the path is either the deployment's `JAX_COMPILATION_CACHE_DIR`
(which JAX reads itself; nothing is set in code then) or one fixed
directory inside the checkout, `<repo>/.jax_cache` (listed in
`.gitignore`), never a temporary or per-process path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile of the process: JAX initializes the
    cache once, at the first compile that consults it.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
