"""Where JAX keeps its persistent compile cache.

A chip run compiles the whole step program, which costs about a minute at
the paper's sizes; the persistent cache lets the next process on the same
machine skip it. The directory is placed from outside the program:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX already keeps its cache
  there and nothing here overrides it;
* otherwise the cache goes to one fixed, git-ignored directory of the
  checkout, ``<repo>/.jax_cache``. The path is part of the cache key, so
  it never depends on a temp dir, a pid or a time.

Entry points call :func:`enable_compile_cache` before their first
compile (JAX decides once per process whether the cache is in use). The
test suite runs with ``JAX_ENABLE_COMPILATION_CACHE=false``, which turns
the cache off whatever the directory.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; return it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
