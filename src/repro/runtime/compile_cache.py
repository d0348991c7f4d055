"""Placement of JAX's persistent compilation cache.

A compiled program is looked up by a key that includes the cache
directory's path, so a cache that moves between runs never hits.  The
rule: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and this module leaves it in charge; otherwise the cache lives at the
fixed ``.jax_cache/`` under the repository root, derived from this
file's location.  Entry points call :func:`enable_compile_cache` once,
before their first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "resolve_cache_dir",
           "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: <repo>/.jax_cache — this file is <repo>/src/repro/runtime/compile_cache.py
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def resolve_cache_dir(environ: Optional[Mapping[str, str]] = None
                      ) -> tuple[str, bool]:
    """``(directory, from_env)``: the environment's directory verbatim
    when ``JAX_COMPILATION_CACHE_DIR`` is set, else the fixed default."""
    env = (os.environ if environ is None else environ).get(CACHE_ENV)
    if env:
        return env, True
    return str(DEFAULT_CACHE_DIR), False


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it: no
    directory is set in code."""
    import jax

    path, from_env = resolve_cache_dir()
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
