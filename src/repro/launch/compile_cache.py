"""JAX's persistent compilation cache for the repo's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise the cache lives at ``<checkout>/.jax_cache``, one
fixed path, so a later run from the same checkout finds what an earlier one
compiled. Entry points (`chip_smoke.py`, `launch/train.py:main`,
`benchmarks/run.py`) call `use_compile_cache` before they compile anything;
importing a package module never does.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The directory the cache uses: the env var when set, else the fixed
    in-checkout path."""
    return os.environ.get(ENV) or str(CHECKOUT / ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory. Takes effect
    only before the process's first compilation (JAX fixes the cache then)."""
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    return compile_cache_dir()
