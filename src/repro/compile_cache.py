"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once at start-up; no
library module touches the cache when it is imported.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The fixed in-checkout cache directory (listed in ``.gitignore``).  The
#: path is part of what JAX keys its entries on, so it never moves.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
