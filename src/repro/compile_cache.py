"""JAX's persistent compilation cache, switched on by entry points.

A fresh process pays every XLA compile again (the fleet sweep, the Pallas
kernels).  Entry points (``chip_smoke.py``, ``benchmarks/*``) call
:func:`enable_compile_cache` once at start-up so a second run reads the
compiled programs back.  Nothing calls it on import, so tests keep the
cache off.
"""
from __future__ import annotations

import os
import pathlib

import jax


def enable_compile_cache(root) -> pathlib.Path:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here.  Otherwise the cache lives at the fixed path
    ``<root>/.jax_cache``.  Every program is cached, however quick its
    compile.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return pathlib.Path(env)
    path = pathlib.Path(root).resolve() / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
