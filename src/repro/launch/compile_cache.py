"""Where JAX's persistent compilation cache lives.

One rule for every entry point: ``JAX_COMPILATION_CACHE_DIR`` wins when it
is set (and no other directory is set in code); otherwise the cache is
``<checkout>/.jax_cache``.  The path is part of the cache's key, so it must
not move between runs.
"""
from __future__ import annotations

import os

import jax

#: the checkout root: src/repro/launch/ -> three levels up
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
