"""Persistent XLA compilation cache for the entry-point scripts.

A jitted episode at TX-GAIA size takes tens of seconds to compile, so the
scripts that users run (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.rl_train`` and ``examples/``) keep compiled programs on
disk, shared by every process that runs from the same checkout.
"""

from __future__ import annotations

import os

import jax

# <checkout>/src/repro/utils/compile_cache.py -> <checkout>
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, because the directory is part
    of what a later process must find again (never a temp name, a pid or
    the time)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
