"""Where JAX keeps its persistent compilation cache.

A compiled step of a full-width model takes a minute or more to build, so
the launchers keep compiled programs across processes.  The directory is
part of the cache's key — one that moves never hits — so it is fixed:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing else is
  set here.
* unset: ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory (see the
    module docstring) before the first compile; returns that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
