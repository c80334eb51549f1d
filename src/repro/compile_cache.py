"""Where the program's entry points keep JAX's persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, before their first
compile: ``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.train``
and ``python -m repro.daemon serve``.  Importing the library never does, so
an embedding application keeps its own cache settings.
"""
from __future__ import annotations

import os
from pathlib import Path

# The checkout's root (this file is <checkout>/src/repro/compile_cache.py).
# A fixed path: the directory is part of each entry's key, so a cache placed
# under a temporary or per-process name would never be hit again.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it from
    the environment and nothing is changed here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
