"""Where JAX keeps its persistent compilation cache.

A directory given from outside (``JAX_COMPILATION_CACHE_DIR``, which JAX
reads itself) wins, and no other is set in code.  Otherwise the cache lives
at the fixed ``<checkout>/.jax_cache``: the path is part of the cache key,
so a directory named after a temporary file, a process or the time would
never be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def compile_cache_dir(environ: Mapping[str, str] = os.environ) -> str:
    """The cache directory for this environment."""
    return environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return the directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
