"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` once at startup; importing ``repro`` never
does. JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when that is
set this sets nothing. Otherwise the cache goes to the fixed
``<repo>/.jax_cache``: never a temp, PID or time-stamped name, because
a cache whose path moves between runs is never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def use_compile_cache(repo_root) -> str:
    """Point JAX's persistent compilation cache at ``repo_root/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` already names one; returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(repo_root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
