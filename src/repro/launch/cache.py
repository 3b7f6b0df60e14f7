"""Persistent compilation cache for the entry points that run on a chip.

``enable_compilation_cache()`` is called by ``chip_smoke.py`` and the
``launch.serve`` / ``launch.train`` mains before anything compiles —
never at package import, so library users and the test suite keep JAX's
defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets nothing. Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path (never a temporary name, a pid or a time), so later runs from
the same checkout find what earlier runs compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compilation_cache"]

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
