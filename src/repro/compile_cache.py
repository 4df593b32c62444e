"""JAX's persistent compilation cache, placed for this checkout.

Entry points (``python -m repro.launch.serve``, the benchmark mains,
``chip_smoke.py``) call :func:`enable` before their first compile; nothing
calls it at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it by itself and this sets nothing. Otherwise the cache is
``<repo>/.jax_cache``: a fixed path, since the directory is part of what a
later run has to find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
