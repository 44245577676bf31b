"""Where JAX's persistent compilation cache lives for this repo's programs.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is left
alone.  Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout: a fixed path, because the path is part of what JAX keys its
entries on, so a directory named after a pid or a time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns that path.

    Call once at program start, before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
