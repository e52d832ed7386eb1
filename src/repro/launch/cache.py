"""Where JAX keeps its persistent compilation cache.

``use_compile_cache()`` is called from the ``main()`` of each entry point
(``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``), never at
import.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it.  Otherwise the cache lives in ``.jax_cache`` at
the root of the checkout (git-ignored): a fixed path, because the path is
part of what a later process must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
