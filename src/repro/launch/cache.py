"""JAX's persistent compilation cache, configured in one place.

Entry points (``chip_smoke.py``, ``examples/federated_mnist.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` at the top of
``main()``; importing this module changes nothing, and the tests call
it only in child processes (``tests/test_cache.py``).  A cold process
on a TPU spends most of its first minute compiling the scan programs;
with the cache on, a second process with the same programs reads them
back instead.
"""

from __future__ import annotations

import os
import pathlib
import re

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/launch/cache.py -> the checkout root.
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as
    its cache directory and no other directory is set.  Otherwise the
    cache lives at ``<checkout>/.jax_cache``: a fixed path, since the
    path is part of what a cached entry is found by.

    Entries are keyed with the programs' op metadata: named scopes and
    source locations.  JAX leaves it out of the key by default, and
    then an executable compiled from the same computation under other
    scope names is read back with its names: a profile attributes
    device time by those stale names (``repro/eval``, ``repro.grad``
    missing from a program cached before they existed).  The checkout's
    own path is cut from the source file names, so a copy of the same
    tree at another path reads the entries back.  The cost: an edit that
    moves a line of traced code (a comment above it is enough) misses
    the cache for every program that traces that code, and the next
    process compiles them anew.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(_CHECKOUT)) + "/")
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
