"""Where JAX keeps its persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (git-ignored). It never moves: the
#: cache is keyed on what is compiled, and a fixed path is what lets a
#: later run of the same checkout find the entries again.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads the variable itself,
    so nothing is set here), else ``CHECKOUT_CACHE``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
