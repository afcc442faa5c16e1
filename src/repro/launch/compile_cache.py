"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` once at start (never at
import).  ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache: JAX
reads the variable itself and nothing here overrides it.  Otherwise the
cache lives at one fixed path inside the checkout, ``<repo>/.jax_cache``
(gitignored) — a path that never depends on a temporary name, a process id
or the time, since the path is part of what a later run looks up.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
