"""JAX's persistent compilation cache for the entry points.

Called from each entry point's ``main`` (``launch/serve.py``,
``launch/train.py``, ``chip_smoke.py``), never at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing here
overrides it. Otherwise the cache lives at one fixed path inside the
checkout, ``<repo>/.jax_cache`` (gitignored): the path is part of what a
cache hit needs, so it is never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
