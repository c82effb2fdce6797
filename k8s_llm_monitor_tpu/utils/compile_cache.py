"""Where the persistent XLA compilation cache lives.

One rule for every entry point (cmd/server.py, benchmarks/, chip_smoke.py):

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; this module
  never overrides it, so whoever starts the process places the cache.
* unset — ``<checkout>/.jax_cache``, resolved from this package's
  location, never from the working directory: the path is part of the
  cache key's surroundings, and the same checkout started from two
  places must find the same cache.  Never a temporary, pid- or
  time-derived directory.
"""

from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_DIR = ".jax_cache"


def compile_cache_dir(configured: str = DEFAULT_DIR) -> str:
    """The directory the cache will use: the environment's when it names
    one, else ``configured`` (relative paths anchor at the checkout)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    return str(REPO_ROOT / configured)


def configure_compile_cache(configured: str = DEFAULT_DIR) -> tuple[str, bool]:
    """Point JAX at the cache before the first jit.  Returns
    ``(directory, was_warm)`` — warm meaning it already held entries."""
    import jax

    path = compile_cache_dir(configured)
    warm = os.path.isdir(path) and any(os.scandir(path))
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path, warm
