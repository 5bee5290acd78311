"""Process-level device setup shared by the entry points.

``use_compile_cache`` places JAX's persistent compilation cache; entry
points call it first, before anything compiles. It is never called at
import time or from tests, so library code and the test suite leave the
process's JAX configuration alone.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

# <repo>/.jax_cache: the cache path is part of each entry's key, so it
# must stay fixed (never a temporary name, a process id or the time)
_DEFAULT_CACHE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here; otherwise the cache lives at the
    fixed in-checkout path ``.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable, not only those that took over a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def free_device_bytes(device=None) -> Optional[int]:
    """Bytes still allocatable on ``device`` (default: the first device),
    from its ``memory_stats()``; None where the backend reports no limit
    (the CPU backend)."""
    stats = (device or jax.devices()[0]).memory_stats()
    if not stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))
