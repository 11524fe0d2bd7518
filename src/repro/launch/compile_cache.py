"""JAX's persistent compilation cache, placed for every entry point.

Each entry point (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``, ``examples/elastic_train.py``,
``benchmarks.run``) calls :func:`enable_compile_cache` before its first
``jit``, so a second run of the same program on the same machine loads
its executables instead of compiling them again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
this module sets nothing.  Otherwise the cache lives at ``CACHE_DIR``, a
fixed path inside the checkout: a path built from a temp name, a pid or
the time would change from run to run, and no run would find what an
earlier one wrote.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> None:
    """Point JAX's persistent compilation cache at ``CACHE_DIR`` unless
    ``JAX_COMPILATION_CACHE_DIR`` already places it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
