"""Where the launchers keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, names the cache and JAX reads it
itself: nothing is configured here.  Otherwise the cache lives in
``.jax_cache/`` at the repository root: a fixed path, so each run finds
what the last one wrote.  Only the launchers and ``chip_smoke.py`` call
:func:`use_compile_cache`; tests keep JAX's default, which caches
nothing on disk.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
