"""Persistent XLA compilation cache for the programs' entry points.

``chip_smoke.py``, ``repro.launch.train`` and ``benchmarks/run.py`` call
``enable_compile_cache`` first thing in ``main``; no library module calls
it, and tests leave the cache off.
"""
from __future__ import annotations

import os
import pathlib

import jax

# a fixed path inside the checkout: a cache directory that moves between
# runs is a cold cache every time
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and no other
    path is set; otherwise the cache lives at ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
