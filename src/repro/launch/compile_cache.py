"""Where JAX keeps its persistent compilation cache.

``enable_compile_cache()`` is called once, before the first compile, by
every entry point that compiles for the chip (``chip_smoke.py``,
``benchmarks/run.py``, ``python -m repro.launch.sgl_serve``).  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is set.  Otherwise the cache goes to ``.jax_cache`` at the root of this
checkout: a fixed path, because the cache is only found again at the same
path (never a temporary, per-process or per-run directory).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
