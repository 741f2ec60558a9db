"""Persistent XLA compilation cache for the entry points.

``launch/train.py``, ``launch/serve.py`` and ``chip_smoke.py`` call
:func:`enable` at start-up (never at import of ``repro``, so the tests do
not write to it). Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
keeps its cache there and nothing is set in code. Otherwise the cache goes
to ``<checkout>/.jax_cache``: a fixed path, because the path is part of
what a later run must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
