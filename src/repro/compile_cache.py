"""Where this repo's programs keep JAX's persistent compilation cache.

Set ``JAX_COMPILATION_CACHE_DIR`` and JAX uses that directory; nothing
here overrides it. Otherwise the cache lives in ``.jax_cache/`` at the
repo root (gitignored). The path is fixed on purpose: it is part of
what a later run must find, so it never comes from a temp name, a pid
or the time.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it. Call once, before the first compile."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
