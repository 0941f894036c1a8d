"""Where JAX's persistent compile cache lives: one rule for every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, places the cache from outside and
JAX reads it by itself, so nothing is set in code.  Otherwise the cache is
``<checkout>/.jax_cache`` (gitignored).  The path is fixed on purpose: it is
what a later process looks the cache up by, so a directory named after a
pid, a time or a ``tempfile`` never hits.
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """The directory the cache resolves to (imports no JAX)."""
    return os.environ.get(CACHE_DIR_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point this process's JAX at the cache, before its first compile.
    Returns the directory in use."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_DIR_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path
