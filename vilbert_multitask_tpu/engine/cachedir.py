"""Where compiled programs persist: one rule for every entry point.

- JAX's persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
  says when the environment sets it (JAX reads the variable itself; this
  module then sets no directory in code). Otherwise it is ``.jax_cache/`` at
  the root of the checkout.
- The AOT executable cache (engine/aotcache.py) defaults to ``.aot_cache/``
  beside it.

Both defaults are fixed, git-ignored paths. The directory is part of the
compilation cache's key, so a path derived from a temp name, a pid, the
clock or a server's state directory is a cache that can never hit.
"""

from __future__ import annotations

import os

import jax
from jax.experimental.compilation_cache import compilation_cache

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# engine/cachedir.py -> engine -> package -> checkout root
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_aot_cache_dir() -> str:
    """The AOT executable cache's home when no explicit
    ``EngineConfig.aot_cache_dir`` (or checkpoint directory) places it."""
    return os.path.join(REPO_ROOT, ".aot_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Idempotent; every serving entry point calls
    it before its first compile. Every compile persists (floor 0 s): the
    small per-bucket programs dominate warmup COUNT, and JAX's 1 s default
    floor would skip them."""
    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
            # JAX latches "is the cache in use" at the process's first
            # compile; one that ran before this call latched "no".
            compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
