"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, the ``repro.launch`` mains) call
:func:`enable_compile_cache` before their first compile; importing
``repro`` never turns the cache on, so library users and the tests keep
JAX's defaults.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
  module leaves it alone.
* Unset: the cache lives at ``<checkout>/.jax_cache`` (ignored by git).
  The path is fixed — never temporary, per-process or time-stamped —
  because it is part of the cache key: a moving directory never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: <checkout>/.jax_cache, from src/repro/launch/compile_cache.py
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
