"""JAX's persistent compilation cache, placed from outside.

Entry points call :func:`enable` before their first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``): a fixed path, never a
temporary or per-process one, so the next run of the same checkout
finds what this one compiled.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    import jax
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
