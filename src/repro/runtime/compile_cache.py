"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before anything compiles; importing
``repro`` never turns the cache on, so tests compile exactly as before.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no directory of its own.
* Unset: the cache lives at a fixed path inside the checkout,
  ``<repo>/.jax_cache`` (listed in ``.gitignore``).  The path is part of
  what makes a later run find an entry, so it is never derived from a
  temp directory, a pid or the clock.
"""

from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
