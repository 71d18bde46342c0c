"""JAX persistent compilation cache, placed from outside or at a fixed path.

Every entry point (``launch.train``, ``launch.serve``, ``launch.fleet``,
``chip_smoke.py``) calls ``enable_compile_cache()`` before it builds
anything.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives
there and nothing else is chosen; otherwise it lives in ``.jax_cache``
at the root of the checkout (git-ignored).  The path is part of the
cache key, so it is never derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Point JAX's persistent compilation cache at ``$ENV`` if set, else
    at ``DEFAULT_DIR``, and return that directory."""
    import jax
    d = Path(os.environ[ENV]) if os.environ.get(ENV) else DEFAULT_DIR
    d.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(d))
    return d
