"""Where this checkout keeps JAX's persistent compilation cache.

One decision, made once per process by the entry scripts (``chip_smoke.py``,
``bench.py``, ``tests/conftest.py``, ``python -m lightgbm_tpu``) before the
first compile: a directory named by ``JAX_COMPILATION_CACHE_DIR`` is JAX's
own business and nothing is set in code; otherwise the cache lives at
``<checkout>/.jax_compile_cache``.  The path is fixed: a cache placed by pid,
time or ``tempfile`` is never found by the next process.
"""

from __future__ import annotations

import os
import pathlib

import jax

_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Point JAX at the compile cache and return the directory in use."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = str(_CHECKOUT / ".jax_compile_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
