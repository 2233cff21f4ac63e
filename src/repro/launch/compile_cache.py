"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``enable_compile_cache()`` once at start-up — never at import, and tests
never call it. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses
that directory, and nothing is changed. Otherwise the cache goes to
``<repo root>/.jax_cache``: a fixed path, never built from a temporary
name, a pid or the time, so a later run of the same checkout finds what an
earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
