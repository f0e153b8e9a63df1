"""JAX persistent compilation cache, placed from outside or at one fixed
path in the checkout.

A compiled program is keyed by (among other things) the cache path, so
the path must not move between runs: ``$JAX_COMPILATION_CACHE_DIR`` when
the environment sets it (JAX reads that variable itself, and no other
directory is set here), else ``var/jax_cache/`` at the repository root
(``var/`` is ignored by git).
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "var", "jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["enable_compile_cache", "DEFAULT_DIR", "ENV_VAR"]
