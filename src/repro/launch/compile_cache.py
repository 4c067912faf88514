"""JAX's persistent compilation cache, at one fixed place per checkout.

A cache is found again only at the same path, so entry points call
:func:`setup` once at start-up (never at import): where
``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and nothing else is
set; otherwise the cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: The default cache directory: ``.jax_cache`` at the root of the checkout.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
