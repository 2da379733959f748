"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles kernels (`Node.start`, `chip_smoke.py`,
`bench.py`, `__graft_entry__.py`) calls `enable()` before its first
compile, so a second run on the same machine reads the shape ladder back
instead of compiling it again. The directory is `$JAX_COMPILATION_CACHE_DIR`
when set, else the fixed `<repo>/.jax_cache`: the path is part of the
cache key, so it never depends on a temp dir, a pid or the time. The
tests switch the cache off with JAX's own `jax_enable_compilation_cache`.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def cache_dir() -> str:
    return os.environ.get(ENV) or DEFAULT_DIR


def enable() -> str:
    """Point JAX's persistent compilation cache at `cache_dir()` and
    cache every compile, however quick; returns the directory."""
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
