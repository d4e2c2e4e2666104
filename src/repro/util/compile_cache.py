"""Where JAX's persistent compilation cache lives for this repository.

Every entry point that compiles at real widths (``chip_smoke.py``,
``launch/serve.py``, ``launch/prune.py``) calls ``enable_compile_cache()``
once, before its first compile:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
  is set in code;
* otherwise the cache goes to the fixed path ``<repo>/.jax_cache``.  The
  path is part of the cache key, so it is never built from a temp name, a
  pid or the time — a directory that moves never hits.

Tests never call it: a compile for a TPU that is described but not attached
can be written to the cache but never read back.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ=os.environ) -> str | None:
    """The directory this process should set, or None when the
    environment variable already names one (JAX then uses it as is)."""
    return None if environ.get(ENV_VAR) else str(REPO_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = compile_cache_dir()
    if path is None:
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    return path
