"""Where the entry points keep JAX's persistent compilation cache.

A cold process compiles every tile program again; the persistent cache
lets a second process of the same checkout read them back instead. The
cache directory is part of what makes an entry findable, so it must not
move between runs: it is either the one the environment names or a
fixed directory at the checkout root. Entry points call
:func:`use_compile_cache` before their first compile; importing the
library never does, so tests stay cache-free.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout root (this file is ``<root>/src/repro/compile_cache.py``).
CHECKOUT_ROOT = Path(__file__).resolve().parents[2]

#: Cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
#: (listed in ``.gitignore``).
CHECKOUT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left alone: JAX reads it
    itself. Otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`.
    Call before the process compiles anything: JAX fixes the cache the
    first time it compiles."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
