"""JAX's persistent compilation cache, placed once for every entry point.

Each entry point (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls
:func:`setup_compile_cache` before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
here overrides it.  Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path (it is part of the cache key, so it
never holds a temporary name, a pid or a time), listed in
``.gitignore``.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CACHE_ENV", "setup_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
