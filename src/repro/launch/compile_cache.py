"""Persistent XLA compilation cache shared by every entry point.

A cold process on the chip spends much of its first minute compiling;
the persistent cache lets a later process with the same programs skip
that. ``enable_compile_cache`` is called once at the top of each entry
point (``chip_smoke.py``, the ``launch/`` CLIs, ``benchmarks/run.py``),
never at import.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here changes. Otherwise the cache lives in ``.jax_cache/`` at the
repository root: a fixed path (the cache key includes nothing that moves
between runs) that ``.gitignore`` lists.
"""

from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
