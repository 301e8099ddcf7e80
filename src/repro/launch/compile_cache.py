"""Where entry points keep JAX's persistent compilation cache.

Called from a program's ``main()`` (``chip_smoke.py``,
``repro.launch.serve``, the ``benchmarks`` mains) — never at library
import, so importing ``repro`` changes no JAX configuration.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
  else is set here.
* Not set: the cache lives at the fixed ``<repo>/.jax_cache`` (listed in
  ``.gitignore``).  The directory is part of what a later run must find,
  so it is never a temporary, per-process or time-stamped path.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
