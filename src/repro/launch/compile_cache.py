"""JAX's persistent compilation cache, placed from outside.

``enable()`` is called first thing by the entry points that compile real
model sizes (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``). The test suite never calls it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# one fixed directory inside the checkout (listed in .gitignore): the
# cache key includes the path, so a directory that moves never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the cache on and return its directory: ``JAX_COMPILATION_CACHE_DIR``
    when it is set, else :data:`DEFAULT_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
