"""JAX's persistent compile cache, placed from outside the program.

A full-width SD-Turbo program takes minutes to compile, so every entry
point (``chip_smoke.py``, ``examples/generate_image.py``,
``repro.launch.serve``) turns the persistent cache on through
:func:`enable` before its first compile:

* when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that
  directory and nothing is set here;
* otherwise the cache lives at one fixed path inside the checkout,
  ``<repo>/.jax_cache`` (ignored by git), so a later run on the same
  machine finds what an earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
