"""JAX's persistent compilation cache for the platform's entry scripts.

Every process of this repository that drives a device (``chip_smoke.py``,
``benchmarks/run.py``) compiles the same wave programs and kernels; the
persistent cache lets a later process skip those compiles.  The cache's
key includes its directory, so the directory must not move between runs.

This is called by entry scripts only, never on library import: a library
that set a process-wide JAX option would surprise its embedder.
"""

from __future__ import annotations

import os
import pathlib


def enable_compile_cache(default_dir: os.PathLike) -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the deployment's choice:
    JAX reads it on its own and this sets no other directory.  Otherwise
    the cache lives at ``default_dir``, a fixed path the caller derives
    from its checkout (never a temporary or per-process one)."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(pathlib.Path(default_dir).resolve())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
