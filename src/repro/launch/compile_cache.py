"""JAX's persistent compilation cache, switched on by the entry points
(``repro.launch.search``, ``chip_smoke.py``, ``benchmarks.run``) and never
at import.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no other
path is set here. Otherwise the cache lives at a fixed, git-ignored path
inside the checkout: the path is part of what a later run looks up, so a
run from the same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile (kernels compile in
    well under the default one-second threshold); returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
