"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles (``repro.launch.serve``,
``benchmarks.run``, ``chip_smoke.py``) calls :func:`setup_compile_cache`
before its first compile, so a second run in the same checkout finds the
first run's executables instead of compiling cold.
"""

from __future__ import annotations

import os

import jax

# fixed, inside the checkout and listed in .gitignore: the directory is
# what a later run has to find again, so it never depends on a temp name,
# a pid or the time
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def setup_compile_cache() -> str:
    """Place the cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: jax reads it itself and
    nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
