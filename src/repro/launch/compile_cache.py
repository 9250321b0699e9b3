"""JAX's persistent compilation cache, at one fixed place.

Entry points (``chip_smoke.py``, the launchers, ``benchmarks/run.py``, the
examples) call ``enable_compile_cache()`` once before their first compile;
library code never does. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this sets nothing. Otherwise the cache goes to
``.jax_cache/`` at the repository root (git-ignored): a fixed path, because
the path is part of every cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
