"""JAX's persistent compilation cache, for the entry scripts.

Entry scripts (``chip_smoke.py``, the ``benchmarks/*_sweep.py`` mains)
call :func:`enable` once before their first compile; importing ``repro``
never touches the cache.  The directory is ``JAX_COMPILATION_CACHE_DIR``
when that is set (JAX reads it itself), and otherwise the fixed
``<repo root>/.jax_cache`` — never a temporary or per-process path, since
the path is part of what makes a later run find the entries.
"""

from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         os.pardir, os.pardir, os.pardir))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
