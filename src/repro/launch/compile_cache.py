"""Where JAX keeps its persistent compilation cache.

The cache path is part of each entry's key, so it must not move between
runs: never a temp, pid- or time-based directory.
"""

from __future__ import annotations

import os

import jax

#: the environment variable JAX itself reads for the cache directory
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: cache directory used when the environment names none
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and nothing
    is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``.
    """
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
