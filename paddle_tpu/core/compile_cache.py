"""Persistent XLA compile cache, placeable from outside.

A chip call starts with no compiled code, and the 760M step programs take
tens of seconds to build — so every entry script that touches the chip
(``chip_smoke.py``, ``bench.py``, ``scripts/bench_760m.py``, ...) calls
:func:`enable` before its first ``jit``.  Never called at package import:
the test suite runs without a persistent cache.

Where the cache lives is the caller's environment's decision first:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself, and
  this module sets **no** directory in code, so a runner that keeps a
  directory across calls gets its hits.
* unset — ``<checkout>/.jax_cache``, a fixed path derived from this
  file's location (the path is part of what makes a cache findable: a
  temp name, pid or timestamp would never hit).  ``.gitignore`` lists it.
"""

from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/paddle_tpu/core/``.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir():
    """The directory :func:`enable` would set in code: ``None`` when the
    environment already names one (JAX reads it), else the fixed
    in-checkout path."""
    return None if os.environ.get(_ENV) else DEFAULT_DIR


def enable():
    """Turn the persistent compile cache on and keep every program (no
    minimum compile time or entry size: the small serving programs are
    worth a file each).  Returns the directory in use."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    path = cache_dir()
    if path is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()  # a cache initialised before this call re-reads config
    return path or os.environ[_ENV]
