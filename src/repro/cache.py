"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``repro.launch.solve``,
``repro.launch.serve_solve``, and each section process of
``benchmarks/run.py``) call
:func:`use_compile_cache` once, before their first compile; importing
``repro`` never does. A second process then finds every program the first one
compiled.
"""
from __future__ import annotations

import os
import pathlib

# fixed, so that the next process looks in the same place
REPO_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
    cache stays there. Otherwise it goes to ``<repo>/.jax_cache``. Every
    program is kept, however fast it compiled: the per-op Pallas kernels
    compile in well under JAX's default one-second threshold.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
