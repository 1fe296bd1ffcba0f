"""JAX's persistent compilation cache, placed once for every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it itself
and nothing here overrides it.  Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (git-ignored).  The directory is part of each
entry's key, so it never depends on a temporary directory, a process id
or the time: a path that moves between runs never hits.
"""
from __future__ import annotations

import os

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
