"""Persistent XLA compilation cache at a path that can be placed from
outside.

``enable()`` is called once, before the first compile, by the entry
points that run on the chip (``chip_smoke.py``, ``benchmarks/run.py``):

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  is set in code;
* otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed path
  (the path is part of the cache key, so a directory that moves never
  hits), listed in ``.gitignore``.

Tests never call it: they compile into the in-memory cache only.
"""

from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> pathlib.Path:
    """Where compiled programs land: the environment's directory when
    ``JAX_COMPILATION_CACHE_DIR`` is set, else ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV)
    return pathlib.Path(env) if env else DEFAULT_DIR


def enable() -> pathlib.Path:
    """Turn the persistent cache on (see module doc); returns its
    directory."""
    if os.environ.get(ENV):
        return cache_dir()
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
