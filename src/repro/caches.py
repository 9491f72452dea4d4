"""Where the program keeps what it caches on disk: inside the checkout.

Two caches persist across processes — JAX's compiled programs and the
kernel autotuner's tile choices (kernels/tune.py). Both live under
``<checkout>/.cache`` (listed in ``.gitignore``), at fixed paths: the
compile cache path is part of its keys, so a directory that moved per
run would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.cache``; this file is ``<checkout>/src/repro/caches.py``
CACHE_ROOT = Path(__file__).resolve().parents[2] / ".cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    it stands; otherwise the cache goes to ``<checkout>/.cache/jax``.
    Entry points (``bench/run.py``, ``chip_smoke.py``) call this before
    their first compile; importing the library never does.
    """
    import jax

    # every program is keyed by a shape class that later runs reuse, so
    # keep the quick compiles too (JAX's default keeps only those >= 1 s)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CACHE_ROOT / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
