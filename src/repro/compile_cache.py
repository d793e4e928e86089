"""Persistent JAX compilation cache for entry-point scripts.

Call :func:`enable` from a script's ``__main__`` path (``chip_smoke.py``,
the benchmark entry points) — never at library import, so importing
``repro`` changes no global JAX state.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no other directory.  Otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (listed in ``.gitignore``): a fixed path is what lets
a later process of the same checkout find the entries again.  Every
compilation is cached, however quick: a Pallas kernel compiles in about a
second, under JAX's default threshold, and a cold run compiles one per
distinct phase shape.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable(repo_root) -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    d = os.environ.get(ENV)
    if not d:
        d = str(Path(repo_root).resolve() / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return d
