"""Persistent XLA compilation cache, placed from outside the program.

Every entry point (``chip_smoke.py``, ``examples/*.py``,
``python -m repro.launch.train``, ``python -m benchmarks.run``) calls
:func:`enable_compile_cache` before its first compile.  Importing
``repro`` does not, so the tests compile without a persistent cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is used and no
other is set.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because a cache directory that moves between runs never
hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> pathlib.Path:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call before the process's first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return pathlib.Path(path)
