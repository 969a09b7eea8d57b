"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins.
Otherwise :func:`enable_compile_cache` points the cache at the fixed
``<checkout>/.jax_cache`` (gitignored): the directory is part of the cache
key, so it must not move between runs. Called by ``chip_smoke.py``,
``repro.launch.serve`` and ``benchmarks/run.py`` — never on import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Make sure compiled programs are cached; returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
