"""JAX's persistent compilation cache, at a directory the caller can set.

Entry points (``chip_smoke.py``, the benchmark harnesses) call
:func:`enable_compile_cache` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in ``.gitignore``): a fixed path, because a later run finds an
entry again only under the same directory.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    where = os.environ.get(ENV_VAR)
    if where:
        return where
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
