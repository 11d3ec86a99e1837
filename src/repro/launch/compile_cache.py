"""JAX's persistent compilation cache, at one fixed place per checkout.

The cache's key includes its directory, so a directory that moves between
runs never hits: the path holds no temporary name, process id or time.
Entry points call ``enable_compile_cache()`` before anything else touches
JAX.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def compile_cache_dir(environ: Mapping[str, str] = os.environ) -> Optional[str]:
    """The directory this process must set, or None where
    ``JAX_COMPILATION_CACHE_DIR`` is set: JAX reads that itself, and the
    code then sets no directory of its own."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return CHECKOUT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = compile_cache_dir()
    if path is None:
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    return path
