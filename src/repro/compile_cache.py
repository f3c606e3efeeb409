"""JAX's persistent compilation cache, at one fixed place per checkout.

An entry point (``chip_smoke.py``) calls :func:`enable_compile_cache`
once before its first compile; the library never does, so importing it
changes no JAX setting.  Where ``JAX_COMPILATION_CACHE_DIR`` is set the
cache lives there and nowhere else; otherwise it lives in
``<repo>/.jax_cache``.  The path must not move between runs: it is part
of what a cached entry is found by.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(REPO_ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
