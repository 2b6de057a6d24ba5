"""Persistent XLA compilation cache for the launchers and ``chip_smoke.py``.

A cold process compiles every program again; the persistent cache lets a
later process reuse what an earlier one compiled.  Only a directory that
never moves can hit, so the default is one fixed path inside the checkout
(``.jax_cache/``, ignored by git) — never a temporary, pid- or time-derived
one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache lives at :data:`CACHE_DIR`.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
