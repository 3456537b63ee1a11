"""JAX's persistent compilation cache for the entry points.

Entry points (the ``repro.launch`` mains, ``chip_smoke.py``, the benchmark
mains) call :func:`enable_compile_cache` once, before their first compile.
Importing ``repro`` never does, so tests compile with whatever the
environment gives them.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here.  Otherwise the cache lives at ``<repo>/.jax_cache``: a fixed
path, because the directory is part of the cache key — a per-run temp, pid or
time-stamped path would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["ENV_VAR", "FALLBACK_DIR", "compile_cache_dir",
           "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
FALLBACK_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def compile_cache_dir() -> str | None:
    """The directory this module would set, or None when the environment
    names one (JAX then uses it unaided)."""
    return None if os.environ.get(ENV_VAR) else FALLBACK_DIR


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    path = compile_cache_dir()
    if path is None:
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    return path
