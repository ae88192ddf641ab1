"""JAX's persistent compilation cache for the process entry points.

``launch.serve``, ``launch.calibrate`` and ``chip_smoke.py`` call
:func:`enable` first thing in ``main()``; the library and the tests never
do, so importing a module turns nothing on.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing else
  is set here.
* Otherwise the cache is ``<checkout>/.jax_cache`` - a fixed path, never a
  temporary, pid- or time-derived one, since the directory is part of what
  a later process must find again.  The directory is git-ignored.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
