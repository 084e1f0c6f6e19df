"""Where JAX keeps its persistent compilation cache.

Every entry point (``repro.launch.serve``, ``benchmarks/run.py``,
``chip_smoke.py``) calls :func:`use_compile_cache` before it compiles
anything.  The cache directory is part of what makes an entry findable
again, so it is a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself, so nothing is set
here), else ``<checkout>/.jax_cache`` — never a temporary name, a pid or a
time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
