"""JAX's persistent compilation cache for the entry points.

:func:`enable_compile_cache` is called first thing in each entry point's
``main`` (never at import, so tests and library users keep JAX's own
configuration).  A cold TPU compile of a full-width decode step takes tens
of seconds; the cache lets the next process on the same machine skip it.
"""
from __future__ import annotations

import os
import pathlib

import jax

# The cache directory is part of each entry's key, so it is a fixed path
# inside the checkout: never a temp name, a pid or a time.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing here overrides it.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
