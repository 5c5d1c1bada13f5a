"""One place that decides where JAX's persistent compilation cache lives.

Every entry point that compiles for a device (train.main, sample,
``python -m nanosandbox_tpu.serve``, bench.py, chip_smoke.py) calls
``enable_compile_cache()`` before its first compile. The directory is
part of the cache key, so it is never a temporary name, a pid or a time:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; this code
    sets nothing, so the cache can be placed from outside;
  * otherwise — ``<checkout>/.jax_cache`` (gitignored).

The CPU test-suite turns the cache off as a whole (tests/conftest.py).
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Returns the directory the cache is kept in."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
