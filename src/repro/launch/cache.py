"""Where JAX keeps its persistent compilation cache, and what keys it.

``use_compile_cache()`` is called from the ``main()`` of each entry point
(``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``), never at
import.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it.  Otherwise the cache lives in ``.jax_cache`` at
the root of the checkout (git-ignored): a fixed path, because the path is
part of what a later process must find again.

The keys include each op's metadata.  JAX strips it by default, so a
program that differs from a cached one only in its ``jax.named_scope``
names would be served the cached executable, and a profile of it would
show the other build's scopes (or none).  Source paths in the metadata are
taken relative to the checkout, so the same tree keys alike wherever it is
unpacked.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(ROOT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
