"""Where compiled programs are kept between runs.

``enable_compile_cache`` is called once, before the first compile, by
every entry point that compiles at full size (the launcher and
``chip_smoke.py``). A second run of the same program then loads its
executable from disk instead of compiling it again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

# fixed, so that every run of this checkout shares one cache: the path is
# part of what JAX keys its entries on
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache
    lives in ``<repo>/.jax_cache``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
