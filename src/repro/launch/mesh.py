"""Device setup: production mesh definitions (single-pod 16x16, multi-pod
2x16x16) and the persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache``: fixed, because the cache directory is part of
#: what a later process must name again to find its entries.
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist (tests / smoke): 1xN data x model."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is changed
    (JAX reads it itself). Otherwise the cache goes to
    ``DEFAULT_COMPILE_CACHE``. Entry points call this from ``main()``,
    never at import, so importing code (tests) stays cache-free."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)
