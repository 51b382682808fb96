"""Where JAX keeps its persistent compilation cache for this repository.

A cache entry is found again only at the same path, so the path is fixed:
the directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX
reads that variable itself), else ``.jax_cache/`` at the repository root.
Entry points call ``configure()`` before their first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Point JAX's persistent compilation cache at its fixed directory.

    Leaves JAX's own reading of ``JAX_COMPILATION_CACHE_DIR`` alone when
    the variable is set; otherwise sets ``jax_compilation_cache_dir`` to
    ``REPO_CACHE``.  Returns the directory in use.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
