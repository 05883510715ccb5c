"""JAX's persistent compilation cache, configured in one place.

Entry points (``chip_smoke.py``, ``examples/federated_mnist.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` at the top of
``main()``; importing this module changes nothing, and the tests never
call it.  A cold process on a TPU spends most of its first minute
compiling the scan programs; with the cache on, a second process with
the same programs reads them back instead.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/launch/cache.py -> the checkout root.
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as
    its cache directory and nothing else is set.  Otherwise the cache
    lives at ``<checkout>/.jax_cache``: a fixed path, since the path is
    part of what a cached entry is found by.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
