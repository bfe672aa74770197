"""JAX's persistent compilation cache, placed from outside.

`enable_compile_cache()` is called at the start of each entry point's
`main()`, never on import. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX
reads it itself and this sets no other directory. Otherwise the cache goes
to `<checkout>/.jax_cache`, a path derived from this package's location and
fixed across runs: the directory is part of what a later run looks up.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
