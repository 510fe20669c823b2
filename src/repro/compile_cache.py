"""JAX's persistent compilation cache for programs run from this checkout."""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path: the cache directory is part of what JAX keys entries on, so
# a directory that moved between runs would never hit.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Keep JAX's compilation cache in ``$JAX_COMPILATION_CACHE_DIR`` when
    that is set (JAX reads the variable itself, so nothing is set here),
    and otherwise in ``.jax_cache/`` at the checkout root.  Returns the
    directory in use.  Entry points call this from ``main``; importing a
    module must not change JAX's configuration."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
