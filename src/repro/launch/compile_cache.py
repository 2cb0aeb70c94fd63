"""JAX's persistent compilation cache for the entry points.

A whole train step of the full-width models takes about a minute to
compile; the cache keeps that compile for the next process.  Entry points
(``chip_smoke.py``, ``repro.launch.train``) call :func:`enable` once
before they compile anything.  Tests never do.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# the cache key includes this path, so it is fixed: one directory in the
# checkout (git-ignored), never a per-run or temporary one
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is already jax's cache
    directory and is left as it is; otherwise the cache goes to
    ``<checkout>/.jax_cache``.

    The key holds the program's metadata too.  A profiler maps device ops
    to the program's named scopes through the executable's ``op_name``
    metadata, and without it in the key a program whose scopes changed
    would load an executable compiled with the old ones.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
