"""JAX's persistent compilation cache, at one fixed place.

Entry points (``chip_smoke.py``, the ``examples/`` scripts) call
:func:`enable` once at start-up; nothing calls it at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets no other path.  Otherwise the cache goes to ``.jax_cache`` at the root
of the checkout (git-ignored): a fixed path, because the path is part of
the cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """The directory the cache uses: the environment's, else the fixed
    in-checkout default."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
