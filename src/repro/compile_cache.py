"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``python -m repro.bench``,
``python -m repro.launch.serve``) calls :func:`configure_compile_cache`
before its first compile. When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it and nothing is set here. Otherwise the cache goes to the
fixed directory ``<repo root>/.jax_cache`` (gitignored): the path is
part of the cache's key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
