"""Placement of JAX's persistent compilation cache.

The operator places the cache with ``JAX_COMPILATION_CACHE_DIR`` (JAX
reads it itself, at import).  When it is unset the cache goes to
``<checkout>/.jax_cache`` — derived from the package location and from
nothing else, because a directory that moves between runs never hits.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Make sure the process has a persistent compile cache and return
    its directory.  Must run before the first compile: JAX latches
    whether a cache is in use at that point.  Idempotent."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax
    if jax.config.jax_compilation_cache_dir != _DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
