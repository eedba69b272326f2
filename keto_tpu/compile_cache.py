"""Where the persistent XLA compilation cache lives.

One rule for the whole program. Where `JAX_COMPILATION_CACHE_DIR` is set,
JAX reads it by itself and nothing here touches the configuration: the
caller has placed the cache. Otherwise the cache is `<checkout>/.jax_cache`,
derived from this package's location: a fixed path, because the directory is
part of what a cache entry is found by, and a directory that moves (a temp
name, a pid, a timestamp) never hits.

A cold daemon compiles one program per kernel family and launch bucket
(tens of seconds each on a TPU at deployment sizes); a restart on the same
checkout finds them here.
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    package_dir = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(package_dir), ".jax_cache")


def ensure_compile_cache() -> str:
    """Place the compile cache before the first compile; returns the
    directory in use. Idempotent."""
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return placed
    import jax

    path = default_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
