"""Placement of jax's persistent compilation cache.

The directory is part of a cache entry's key in practice: a cache that
moves between runs never hits. So it is placed exactly one of two ways,
and never from ``tempfile``, a pid or the time:

  * ``JAX_COMPILATION_CACHE_DIR`` set: jax reads the variable itself and
    this module sets nothing — whoever runs the program owns the place;
  * unset: one fixed directory inside the checkout, ``<repo>/.jax_cache``
    (git-ignored), the same from every process of every run.

Entry points call :func:`setup_compile_cache` before their first use of
jax (``chip_smoke.py``); the launcher exports
:func:`cache_dir` to its chip-using children as
``JAX_COMPILATION_CACHE_DIR`` so that they need no call of their own.
"""
from __future__ import annotations

import os

__all__ = ["cache_dir", "setup_compile_cache", "cache_entries"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """Where compiled programs are kept: the environment's directory,
    else the fixed in-checkout one."""
    return os.environ.get(_ENV) or os.path.join(_REPO, ".jax_cache")


def setup_compile_cache() -> str:
    """Point jax at :func:`cache_dir` (a no-op where the environment
    already does) and return the directory."""
    if not os.environ.get(_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    return cache_dir()


def cache_entries() -> int:
    """Number of compiled programs in the cache directory now."""
    try:
        return sum(1 for n in os.listdir(cache_dir())
                   if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
