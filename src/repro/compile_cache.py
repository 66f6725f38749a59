"""Persistent compilation cache for the command-line entry points.

Every entry point (``chip_smoke.py``, ``python -m repro.experiments``,
``python -m repro.sweeps``, ``python -m repro.launch.serve`` and the
benchmarks' ``bench_main``) calls :func:`enable_compile_cache` once, so
processes that compile the same programs share them. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
else is configured; otherwise the cache sits at a fixed directory of the
checkout (``<checkout>/.jax_cache``, git-ignored) — fixed because the
path is part of the cache key, so a directory that moves never hits.
Library code and the tests never turn it on.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on; returns its directory, or ``None``
    when there is neither the variable nor a checkout to hold it (an
    installed package writes nothing outside its checkout)."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    if not (CHECKOUT / "pyproject.toml").is_file():
        return None
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # spawned children (sweep workers) read the same directory
    os.environ[ENV] = path
    return path
