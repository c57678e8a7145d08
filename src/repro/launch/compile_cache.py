"""JAX's persistent compilation cache, placed by the entry points.

The library never turns the cache on at import; scripts call
``enable_compile_cache()`` once before their first compile.  The
directory is part of every entry's key, so it is fixed: the
``JAX_COMPILATION_CACHE_DIR`` JAX already reads when it is set, and
otherwise ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env  # JAX picked it up from the environment already
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
