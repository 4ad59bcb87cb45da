"""JAX's persistent compilation cache, turned on in one place.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, `examples/*.py`,
`python -m repro.launch.serve`) call `enable_compile_cache()` once, before
their first compile, so a second run of the same shapes loads compiled
executables instead of compiling again.  Library imports and tests never
call it.

The directory is `$JAX_COMPILATION_CACHE_DIR` when that is set (JAX reads
it itself; no other directory is set in code), and otherwise the fixed
`<checkout>/.jax_cache`, which `.gitignore` lists.  The path is part of
JAX's cache key, so it is never derived from a temp name, a process id or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; -> its directory.
    Every compile is cached, however short: the mapspace kernels compile
    in well under JAX's default one-second threshold."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
