"""Where JAX keeps its persistent compilation cache.

Entry points (the experiments CLI, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`enable_compile_cache` once before they
compile; importing this module changes nothing.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
leaves it alone.  Otherwise the cache goes to ``.jax_cache/`` at the
checkout root: a fixed path, because the path is part of what a later
process must find again (gitignored).
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
