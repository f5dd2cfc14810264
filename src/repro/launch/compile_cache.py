"""JAX's persistent compilation cache for the entry points.

A compiled program is cached on disk under a key that includes the
cache's path, so the path must not move between runs: it is the one
``JAX_COMPILATION_CACHE_DIR`` names when that is set, and otherwise a
fixed directory inside the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

#: the checkout this package was loaded from (``src/repro/launch/..``)
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache(root: os.PathLike | str = REPO_ROOT) -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR``, else ``<root>/.jax_cache``. Call
    before the first compile; returns the directory."""
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(os.fspath(root), ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
