"""Where JAX's persistent compilation cache lives.

Entry points (the serve and train CLIs, the benchmark harness,
``chip_smoke.py``) call :func:`enable` before their first compile.  Tests
never call it, so test runs leave no cache behind.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  A fixed path:
#: a later run in the same checkout finds what an earlier one compiled.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to :data:`CACHE_DIR`."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
