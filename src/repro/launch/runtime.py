"""Process start-up shared by every entry point: chip_smoke.py, examples/,
benchmarks/ and the launch CLIs call ``init()`` before their first JAX
computation.

* Compile cache.  A process on the chip spends minutes compiling the graph
  build, the engine run and the search programs; JAX's persistent cache
  keeps them across processes.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
  JAX reads it itself and nothing is set here.  Otherwise the cache lives
  at a fixed ``.jax_cache/`` in the checkout root (listed in .gitignore):
  the path is part of each entry's key, so a temporary, pid- or time-named
  directory would never hit.

Numerics are not set here: the library passes float32 matmul precision at
each of its matmuls (``kernels.ref.HIGHEST``), whatever the entry point.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def init() -> None:
    """Place the compile cache."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
