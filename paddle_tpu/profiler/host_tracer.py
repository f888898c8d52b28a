"""Host-side span tracer: the one span primitive of the package.

Reference analogue: ``HostTracer`` collecting ``RecordEvent`` annotations
(platform/profiler/host_tracer.cc) merged into an event tree and exported by
``ChromeTracingLogger`` (profiler/chrometracing_logger.h:32) plus the
aggregate stats tables.

Design: a span is a ``perf_counter_ns`` [begin, end) interval on one thread.
Sites call ``span("jit.step")`` in a ``with`` block.  A span is live whenever
someone is profiling: a ``host_tracer.start()`` session is open, or a
``jax.profiler`` session is running (the benchmark's tracer, ``POST
/profile``, ``Profiler(targets=[TPU])``: anything that calls
``jax.profiler.start_trace``).  Otherwise, or when the site's ``level``
exceeds ``FLAGS_host_trace_level``, ``span`` returns a shared no-op singleton
(no allocation, no record: one integer compare and one ``is_enabled()``
call), so steady-state training and serving pay nothing.

A live span does two things.  Under a profiler session it enters
``jax.profiler.TraceAnnotation(name, **counts)``, so it lands on the host
thread of the same ``.xplane.pb`` as the device's ops, on the device
trace's clock, with the counts given when it opened as the event's stats.
And it is kept in memory as ``(name, tid, start_ns, end_ns, depth,
counts)``: nesting depth comes from a per-thread stack, which also serves
as the "span context" the NaN/Inf guard reports; ``counts`` is a small
dict (or ``None``) of what the site counted, given when the span opens or
with ``note(**counts)`` before it closes.  The store is bounded
(``STORE_LIMIT``, oldest dropped) and read with ``events()``.

A **lifecycle span** (``level=0``) marks something that happens a handful
of times in a process: the package's import, a model's or an engine's
construction, the first build of a program, a train step's first call.
It is always kept, profiler or not and whatever the flag says, because
set-up is over before any profiler starts: in a small store of its own
(``lifecycle()``, ``LIFECYCLE_LIMIT``, never cleared by ``start()``), and
in the session store and the trace as well when someone is profiling.  A
site that learns only afterwards that a call was a first one (or that is
older than this module, as the package's own import is) stamps
``perf_counter_ns`` itself and hands the stamps to ``lifecycle_since``.  A
lifecycle span must never sit on a per-step or per-token path.

Export: ``to_chrome_trace()`` renders events as chrome://tracing /
perfetto "X" complete events (one pid, real thread ids, metadata rows);
``summary()`` renders the Paddle-style stats table (count/total/avg/max/min
per span name).
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

from ..core import flags as _flags

# span() tests _LEVEL[0] (the flag), then _COLLECTING[0] (a start() session)
# or the profiler's own switch.
_LEVEL = [1]
_COLLECTING = [False]
STORE_LIMIT = 1 << 16
LIFECYCLE_LIMIT = 1 << 10
_EVENTS: collections.deque = collections.deque(maxlen=STORE_LIMIT)
_LIFECYCLE: collections.deque = collections.deque(maxlen=LIFECYCLE_LIMIT)
_THREAD_NAMES: dict[int, str] = {}
_TLS = threading.local()


def _on_level_change(value):
    _LEVEL[0] = int(value)


_flags.register_flag_observer("FLAGS_host_trace_level", _on_level_change)


def get_level() -> int:
    return _LEVEL[0]


def set_level(level: int):
    _flags.set_flags({"FLAGS_host_trace_level": int(level)})


class _NullSpan:
    """Shared do-nothing context manager — the disabled-tracing fast path."""

    __slots__ = ()
    live = False   # a site asks before it counts something only for note()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **counts):
        pass


_NULL = _NullSpan()


class _Span:
    __slots__ = ("name", "counts", "live", "_ann", "_lifecycle", "_t0",
                 "_depth")

    def __init__(self, name, counts, profiled, live, lifecycle):
        self.name = name
        self.counts = counts
        # counts given at the open ride into the trace as the event's
        # stats; those of a later note() stay in the store
        self._ann = (TraceAnnotation(name, **(counts or {})) if profiled
                     else None)
        self.live = live   # kept in the session store (someone is profiling)
        self._lifecycle = lifecycle
        self._t0 = 0
        self._depth = 0

    def note(self, **counts):
        """Counts taken where the work happened, attached to this span."""
        if self.counts is None:
            self.counts = counts
        else:
            self.counts.update(counts)

    def __enter__(self):
        stack = getattr(_TLS, "stack", None)
        if stack is None:
            stack = _TLS.stack = []
        self._depth = len(stack)
        stack.append(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        stack = _TLS.stack
        if stack and stack[-1] is self.name:
            stack.pop()
        _keep((self.name, _tid(), self._t0, end, self._depth, self.counts),
              self.live, self._lifecycle)
        return False


def _tid():
    tid = threading.get_ident()
    if tid not in _THREAD_NAMES:
        _THREAD_NAMES[tid] = threading.current_thread().name
    return tid


def _keep(event, live, lifecycle):
    if live:
        _EVENTS.append(event)
    if lifecycle:
        _LIFECYCLE.append(event)


def span(name: str, level: int = 1, **counts):
    """Open a trace span; returns the no-op singleton when nobody is
    profiling or the site's ``level`` exceeds ``FLAGS_host_trace_level``.
    ``level=0`` is a lifecycle span: always kept (module docstring)."""
    if _LEVEL[0] < level:
        return _NULL
    profiled = TraceAnnotation.is_enabled()
    live = profiled or _COLLECTING[0]
    if not (live or level == 0):
        return _NULL
    return _Span(name, counts or None, profiled, live, level == 0)


def lifecycle_since(name: str, t0_ns: int, t1_ns: int | None = None,
                    **counts):
    """Keep the lifecycle span ``name`` that began at ``t0_ns`` and ended at
    ``t1_ns`` (``perf_counter_ns`` stamps the site took itself), or ends
    now."""
    _keep((name, _tid(), t0_ns, t1_ns or time.perf_counter_ns(),
           len(getattr(_TLS, "stack", ())), counts or None),
          enabled(0), True)


def enabled(level: int = 1) -> bool:
    return _LEVEL[0] >= level and (_COLLECTING[0]
                                   or TraceAnnotation.is_enabled())


def current_stack() -> list:
    """Names of the spans currently open on THIS thread, outermost first
    (the context the NaN/Inf guard attaches to its error)."""
    return list(getattr(_TLS, "stack", ()))


# -- collection sessions ----------------------------------------------------
def start():
    """Begin a collection session; drops whatever the store held."""
    _EVENTS.clear()
    _THREAD_NAMES.clear()
    _COLLECTING[0] = True


def stop() -> list:
    """End the session; returns the collected event tuples."""
    _COLLECTING[0] = False
    return list(_EVENTS)


def is_collecting() -> bool:
    return _COLLECTING[0]


def events() -> list:
    """Snapshot of the store: the spans completed since the last ``start()``
    (or, with no session of this module's, under profiler sessions), the
    newest ``STORE_LIMIT`` of them."""
    return list(_EVENTS)


def span_count() -> int:
    return len(_EVENTS)


def lifecycle() -> list:
    """Snapshot of the lifecycle spans of this process, oldest first."""
    return list(_LIFECYCLE)


# -- export -----------------------------------------------------------------
def to_chrome_trace(evts=None, process_name="paddle_tpu") -> dict:
    """Render events as a chrome://tracing trace-event JSON object
    (loadable in chrome://tracing and https://ui.perfetto.dev)."""
    if evts is None:
        evts = events()
    pid = os.getpid()
    out = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": process_name}}]
    for tid, tname in sorted(_THREAD_NAMES.items()):
        out.append({"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                    "args": {"name": tname}})
    for name, tid, t0, t1, depth, counts in evts:
        out.append({"ph": "X", "name": name, "cat": "host", "pid": pid,
                    "tid": tid, "ts": t0 / 1000.0,
                    "dur": max(t1 - t0, 0) / 1000.0,
                    "args": {"depth": depth, **(counts or {})}})
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def export_chrome(path, evts=None):
    trace = to_chrome_trace(evts)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


def summary(evts=None, sorted_by="total", time_unit="ms") -> str:
    """Paddle-style aggregate stats table: per span name, call count and
    total/avg/max/min duration (reference: the profiler summary tables)."""
    if evts is None:
        evts = events()
    if not evts:
        return "(no host trace events recorded)"
    div = {"s": 1e9, "ms": 1e6, "us": 1e3, "ns": 1.0}.get(time_unit, 1e6)
    agg: dict[str, list] = {}
    for name, _tid, t0, t1, _d, _c in evts:
        dur = max(t1 - t0, 0)
        st = agg.get(name)
        if st is None:
            agg[name] = [1, dur, dur, dur]
        else:
            st[0] += 1
            st[1] += dur
            st[2] = max(st[2], dur)
            st[3] = min(st[3], dur)
    key = {"total": lambda kv: -kv[1][1], "count": lambda kv: -kv[1][0],
           "max": lambda kv: -kv[1][2], "name": lambda kv: kv[0]}
    rows = sorted(agg.items(), key=key.get(sorted_by, key["total"]))
    wname = max(24, max(len(n) for n in agg) + 2)
    header = (f"{'Name':<{wname}}{'Calls':>8}{'Total(' + time_unit + ')':>14}"
              f"{'Avg(' + time_unit + ')':>12}{'Max(' + time_unit + ')':>12}"
              f"{'Min(' + time_unit + ')':>12}")
    bar = "-" * len(header)
    lines = [bar, "Host Tracer Summary".center(len(header)), bar, header, bar]
    for name, (cnt, tot, mx, mn) in rows:
        lines.append(f"{name:<{wname}}{cnt:>8}{tot / div:>14.3f}"
                     f"{tot / cnt / div:>12.3f}{mx / div:>12.3f}"
                     f"{mn / div:>12.3f}")
    lines.append(bar)
    return "\n".join(lines)
