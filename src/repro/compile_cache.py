"""Persistent XLA compile cache for the chip entry points.

``enable()`` is called by ``chip_smoke.py`` and the benchmark CLIs (never by
the test suite). Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
keeps its cache there and nothing is set here. Otherwise the cache lives in
``.jax_cache/`` at the root of this checkout: a fixed path, so a later run
from the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
