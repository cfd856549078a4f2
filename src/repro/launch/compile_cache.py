"""JAX's persistent compilation cache, placed where a caller can find it again.

``enable()`` is called once by each entry point (``chip_smoke.py``,
``serve_rec.main``, ``train.main``, ``benchmarks.run``).  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else is
set here.  Otherwise the cache lives at one fixed path inside the checkout,
``<repo>/.jax_cache`` (git-ignored): the path is part of what makes a later
run hit, so it is never built from a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
