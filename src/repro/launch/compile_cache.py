"""The persistent XLA compile cache, in one place.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, nothing is
set here. Otherwise the cache lives at one fixed path inside the checkout,
``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is part of what a
cached entry is found by, so it never depends on a pid, the time or a
temporary name.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<repo>/.jax_cache``: src/repro/launch/ is three levels below."""
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    return os.path.join(root, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
