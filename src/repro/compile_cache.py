"""Where JAX keeps its persistent compilation cache.

``enable()`` is the first call of every entry point that compiles for the
chip (``chip_smoke.py``, ``launch/serve.py``, ``launch/quantize.py``):

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory itself
  and nothing here sets another;
* otherwise the cache goes to ``<repo root>/.jax_cache`` (git-ignored), a
  path fixed by this file's location — never a temp name, a pid or a time
  — so every run from the same checkout finds what earlier runs compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent cache at its directory; returns that path."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
