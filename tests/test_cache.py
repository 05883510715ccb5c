"""The persistent compilation cache's key (``repro.launch.cache``).

Each case compiles a small program in a child process, from a
checkout that holds only ``cache.py`` and the program, and then again
from a second checkout against the same cache directory: a copy of the
tree at another path reads the entry back, while a renamed scope (the
same computation, so stale op names were it read back) or a moved line
compiles anew.
"""

import os
import shutil
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src", "repro", "launch", "cache.py")

PROGRAM = '''import jax
import jax.numpy as jnp


@jax.jit
def f(x):
    with jax.named_scope("repro/{scope}"):
        return jnp.sin(x) * 2
'''

RUN = '''import os, sys
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
import jax
import numpy as np
from repro.launch import cache
hits = []
jax.monitoring.register_event_listener(
    lambda e, **kw: hits.append(e)
    if e == "/jax/compilation_cache/cache_hits" else None)
cache.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
import program
jax.block_until_ready(program.f(np.ones(3, np.float32)))
print(len(hits))
'''


def _checkout(root, program: str) -> str:
    os.makedirs(os.path.join(root, "src", "repro", "launch"))
    shutil.copy(SRC, os.path.join(root, "src", "repro", "launch"))
    with open(os.path.join(root, "program.py"), "w") as f:
        f.write(program)
    return str(root)


def _hits(root, cache_dir) -> int:
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", RUN, root], env=env,
                         capture_output=True, text=True, check=True)
    return int(out.stdout.split()[-1])


@pytest.mark.parametrize("second,hits", [
    ("moved", 1),          # the same tree at another path
    ("renamed_scope", 0),  # the same computation under another name
    ("moved_line", 0),     # a line added above the traced code
])
def test_compile_cache_key(tmp_path, second, hits):
    first = _checkout(tmp_path / "a", PROGRAM.format(scope="eval"))
    assert _hits(first, tmp_path / "cache") == 0
    program = {"moved": PROGRAM.format(scope="eval"),
               "renamed_scope": PROGRAM.format(scope="eval2"),
               "moved_line": "\n" + PROGRAM.format(scope="eval")}[second]
    assert _hits(_checkout(tmp_path / "b", program),
                 tmp_path / "cache") == hits
