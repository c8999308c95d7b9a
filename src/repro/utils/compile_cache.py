"""JAX's persistent compile cache, placed for the command-line entry points.

The entry points (``chip_smoke.py``, ``python -m benchmarks.run``,
``python -m repro.xp`` and the ``repro.launch`` CLIs) call
:func:`enable_compile_cache` once, before their first compile.  Importing
``repro`` never turns the cache on, so library callers and the tests keep
JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (git-ignored).  The path is fixed: a cache is only
# found again by a later run that looks in the same place.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` when it is set, and no other directory;
    otherwise :data:`DEFAULT_DIR` inside the checkout.
    """
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
