"""Where JAX's persistent compilation cache lives.

The cache key includes the directory's path, so a directory that moves
never hits: the path is either the one the environment names or one
fixed place in the checkout — never a temporary, pid- or time-derived
path. Every entry point that compiles for the chip (``chip_smoke.py``,
``bench.py``, the serving member child) calls :func:`enable_compile_cache`
before its first compilation.
"""

from __future__ import annotations

import os

import jax

__all__ = ["enable_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Return the cache directory in force. If ``JAX_COMPILATION_CACHE_DIR``
    is set, do nothing — JAX reads it and this code sets no other path.
    Otherwise point ``jax_compilation_cache_dir`` at
    ``<checkout>/.jax_cache`` (git-ignored) and export the variable so
    child processes share it."""
    path = os.environ.get(_ENV)
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    os.environ[_ENV] = path
    return path
