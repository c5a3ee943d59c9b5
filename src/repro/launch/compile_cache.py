"""JAX's persistent compilation cache for the entry points.

Call :func:`enable` from a program's ``main`` (never at import).  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set in code; otherwise the cache lives at a fixed directory inside the
checkout (``<repo>/.jax_cache``, git-ignored).  The path is part of the
cache key, so it never depends on a temporary name, a process id or the
time.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["ENV", "DEFAULT_DIR", "enable"]

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
