"""JAX's persistent compilation cache for this repository's entry points.

Entry points (``chip_smoke.py``, ``bench/run.py``, ``launch/*``,
``examples/*``) call :func:`enable_compile_cache` once at start; nothing
calls it at import. Compiled kernels and programs are then written to and
read back from one fixed directory, so a second run on the same machine
skips compiling what the first run compiled.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: a fixed path, since the path is part of each
# cache entry's key and a directory that moves never hits.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` where it is set (JAX reads it itself),
    otherwise ``<checkout>/.jax_cache``. Every compile is cached, however
    short: a Pallas kernel compiles in about a second."""
    path = os.environ.get(CACHE_ENV) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
