"""Where JAX keeps its persistent compile cache.

Without it every process compiles each of its programs afresh, so every
entry point turns the persistent cache on before its first compile. A
cache that moves never hits, so the path is fixed:
`JAX_COMPILATION_CACHE_DIR` when the environment sets it (JAX reads it
itself), otherwise `.jax_cache` at the root of the checkout this package
lives in -- never the working directory, a pid, a temporary name or the
time.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["enable_compile_cache"]

#: root of the checkout: this file is <checkout>/src/repro/runtime/
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    Call from an entry point before the first compile, never on import.
    Where `JAX_COMPILATION_CACHE_DIR` is set nothing is set in code."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
