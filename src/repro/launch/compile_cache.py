"""Where JAX's persistent compilation cache lives.

A cache entry is found again only at the path it was written to, so the
path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself), else ``<checkout>/.jax_cache`` (git
ignores it).  Called at the start of each entry point, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the repository checkout this package was loaded from (src/repro/launch/)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
