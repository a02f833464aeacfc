"""Persistent XLA compilation cache for the command-line entry points.

Compiling the full-width serving step and the Pallas kernels takes a
large share of a cold run on the chip.  Entry points call
`configure_compile_cache()` once, before their first compile, so a later
run over the same programs loads them instead of compiling again.
Library code and tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# the checkout root: src/repro/launch/compile_cache.py -> parents[3]
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When $JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to the fixed
    `<checkout>/.jax_cache` (listed in .gitignore): a directory that
    moved between runs would never be found again, so the path carries
    no process id, time or temporary name.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
