"""Where JAX keeps its persistent compilation cache.

Programs (``chip_smoke.py``, the ``examples/`` and ``benchmarks/`` mains)
call :func:`enable` once at start-up; importing ``repro`` never does.  A
cache directory given in ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads the
variable itself); otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``, so every run from one checkout finds the
programs an earlier run compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
