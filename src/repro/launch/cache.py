"""Where compiled programs are kept between runs.

JAX's persistent compilation cache is keyed in part by its directory, so
the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself), else ``.jax_cache`` at the root
of this checkout.  The entry points call :func:`enable_compile_cache`;
importing ``repro`` never does, and neither do the tests.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
