"""What the program did before the measured window opened, by phase, from
its own account (``setup.*``; each moves ``setup_s``; no trace needed).

``paddle_tpu.core.compile_cache.log()`` holds one record per jaxpr trace,
lowering, backend compile (or the cache load that replaced it) and cache
hit or miss that JAX made in this process, stamped on ``perf_counter_ns``
when it ended; ``profiler.host_tracer.lifecycle()`` holds the package's
own import as the span ``setup.import`` and, inside it, the package's first
touch of the device as ``setup.first_device_touch``.  The window opens at
``obs["t_start"]`` on the same clock, and only what ended before it counts:

* ``setup.first_device_touch_s``  the draw of the default generator's key
  at import: the backend's start where the package is the first to touch
  the device (the ``train`` kind imports it before the harness asks for
  the device), next to nothing where it is not.
* ``setup.package_import_s``  ``import paddle_tpu`` (without ``jax`` where
  that was imported first) less that first touch: the package alone.
* ``setup.trace_lower_s``     seconds covered by traces and lowerings (a
  trace inside a trace is covered once).
* ``setup.backend_compile_s`` seconds covered by backend compiles, cache
  loads included.
* ``setup.programs``          backend compile requests (executables built
  or loaded).
* ``setup.cache_misses``      misses of the persistent cache (0 when warm).

These add up to less than ``setup_s``: the rest is the interpreter's
start, ``import jax``, the device's start where the harness asked first,
the weights, and the warm-up's own steps on the device.  A program without
the account (any commit before PR 26) gives ``None`` for every member.
"""

from benchmark import trace_reduce
from paddle_tpu.core import compile_cache
from paddle_tpu.profiler import host_tracer

# member -> the log's phases whose covered seconds, or whose records, it is
SECONDS = {"setup.trace_lower_s": ("trace", "lower"),
           "setup.backend_compile_s": ("backend",)}
COUNTS = {"setup.programs": "backend", "setup.cache_misses": "cache_miss"}


def _records(obs):
    """The log's records that ended before the window opened, or ``None``
    where the program keeps no log or the log has dropped its oldest."""
    log = getattr(compile_cache, "log", None)
    if log is None:
        return None
    records = log()
    if len(records) >= compile_cache.LOG_LIMIT:
        return None
    return [r for r in records if r[2] / 1e9 <= obs["t_start"]]


def _lifecycle_s(name, obs):
    """Seconds of the first lifecycle span ``name`` that ended before the
    window opened, or ``None``."""
    for n, _, t0, t1, *_ in getattr(host_tracer, "lifecycle", list)():
        if n == name and t1 / 1e9 <= obs["t_start"]:
            return (t1 - t0) / 1e9
    return None


def read(name, obs, cell, cfg, peak):
    if name == "setup.first_device_touch_s":
        return _lifecycle_s("setup.first_device_touch", obs)
    if name == "setup.package_import_s":
        whole = _lifecycle_s("setup.import", obs)
        touch = _lifecycle_s("setup.first_device_touch", obs)
        return None if whole is None else whole - (touch or 0.0)
    records = _records(obs)
    if records is None:
        return None
    if name in SECONDS:
        # the union of the intervals: a trace inside a trace counts once
        return sum(e - s for s, e in trace_reduce.merge(
            (end / 1e9 - seconds, end / 1e9)
            for _, phase, end, seconds in records if phase in SECONDS[name]))
    if name in COUNTS:
        return float(sum(r[1] == COUNTS[name] for r in records))
    return None
