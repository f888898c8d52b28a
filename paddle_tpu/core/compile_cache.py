"""Persistent XLA compile cache, placeable from outside, and the account of
what JAX traced, lowered, compiled and loaded.

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

**The account.**  JAX times every trace, lowering and backend compile and
counts every hit and miss of the persistent cache, and hands them to
whoever listens (``jax.monitoring``).  Importing this module (the package
does, as the last thing its own import does) registers two listeners that
keep them in one place, a bounded log, :func:`log`: a count or a sum over
any stretch of the process is a pass over it, so there are no counters
beside it.  A listener runs only when JAX traces, lowers, compiles or
reads its cache: never on a step that does none of these.  ``backend``
spans the backend's compile *or* the cache load that replaced it, so
``cache_load`` lies inside a ``backend`` interval and is not added to it.
"""

from __future__ import annotations

import collections
import os
import time

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/paddle_tpu/core/``.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


LOG_LIMIT = 1 << 16   # a cell's set-up leaves about 5,000 records
_LOG: collections.deque = collections.deque(maxlen=LOG_LIMIT)

# jax.monitoring event -> phase in the log
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}


def _on_duration(event, duration_secs, fun_name=None, **_):
    phase = _PHASES.get(event)
    if phase is not None:
        _LOG.append((fun_name, phase, time.perf_counter_ns(), duration_secs))


def _on_event(event, **_):
    _on_duration(event, 0.0)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def log():
    """``[(fun_name, phase, end perf_counter_ns, seconds)]``, oldest first,
    the newest ``LOG_LIMIT``: one record per trace (``"trace"``), lowering
    (``"lower"``), backend compile or load (``"backend"``) and read of the
    persistent cache (``"cache_load"``) that JAX made in this process, and
    one of zero seconds per hit and miss of that cache (``"cache_hit"``,
    ``"cache_miss"``; JAX does not say whose).  ``fun_name`` is JAX's:
    ``decode``, ``jit(step_fn)``, ...  A reader takes what ended before the
    instant it cares about."""
    return list(_LOG)


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
