"""Profiler (reference: python/paddle/profiler/profiler.py:346 — host tracer +
CUPTI merged into chrome traces; here: a real host-side span tracer with
chrome-trace export and stats tables, plus the always-on counter registry).

Pieces:

* ``host_tracer`` — the one span primitive (``span``; ``RecordEvent`` wraps
  it): thread-aware, on ``perf_counter_ns``, live whenever someone is
  profiling (a ``host_tracer.start()`` session or any ``jax.profiler``
  session, whose ``.xplane.pb`` it then also lands in), gated by
  ``FLAGS_host_trace_level`` (0 = off), exported as valid chrome://tracing
  JSON and summarized as a Paddle-style stats table.
* ``counters`` — process-global counter/gauge registry fed by the jit /
  static / io / distributed / optimizer hot paths (compile counts, cache
  hits, retraces, host syncs, device_put bytes, prefetch stalls, ...).
* ``metrics`` — the telemetry layer on top of the registry: mergeable
  log-bucket ``Histogram`` (p50/p95/p99 for serving TTFT / inter-token
  latency / queue wait / checkpoint latency), ``MetricsLogger`` (JSONL
  per-step train metrics accumulated in-graph by
  ``jit.CompiledTrainStep(metrics=...)``), Prometheus text exposition,
  and per-compiled-program HBM/compile/FLOPs telemetry
  (``memory_summary()``, gated by ``FLAGS_device_telemetry``).
* ``devicetime`` — the device-time & efficiency plane: a per-program
  ``ProgramLedger`` noted at every compiled-program dispatch site
  (``FLAGS_device_time_sample=N`` fences every Nth dispatch; 0 = one
  cached read, zero counters), joining sampled wall time with the AOT
  FLOPs/HBM stats into live MFU / achieved-TFLOP/s / HBM-GB/s /
  roofline gauges, a Paddle-style ``summary()`` table, bench-leg
  attribution blocks, and a single-flight ``capture_profile`` XPlane
  window (``POST /profile``).
* ``flight`` — always-on flight-recorder ring buffer; faults (trainer
  recovery, nan/inf raise, fleet replica death/stall) dump a postmortem
  JSON bundle (``scripts/flight_dump.py`` pretty-prints it).
* ``trace`` — per-request distributed tracing: a ``TraceContext`` minted
  at fleet/engine admission, lifecycle child spans (queue, KV reserve,
  prefill chunks, decode iterations, re-prefill after respawn) recorded
  into per-request span trees; head sampling via
  ``FLAGS_request_trace_sample`` + tail-based keep-always for
  deadline-breaching / errored / retried requests; JSONL and merged
  chrome://tracing export on the host tracer's clock.
* ``goodput`` — ``GoodputLedger``: exclusive-time wall-clock buckets
  (compile / step / data_wait / ckpt_sync / restore_replay / recovery /
  idle) for the FaultTolerantTrainer; goodput fraction + >=99%-accounted
  chaos gate.
* ``health`` — the derived-signals layer: ``HealthMonitor`` snapshot
  ring over the whole registry, windowed deltas/rates/percentiles,
  multi-window burn-rate ``SLO`` objectives, live invariant
  ``Watchdog``s (retrace storm, KV block conservation, goodput
  accounting, speculative-acceptance collapse), alert lifecycle with
  flight-dump postmortems, and the single ``admission_level``
  recommendation (gated by ``FLAGS_health``; zero-overhead off).
* ``ops`` — ``OpsServer``: stdlib-HTTP live endpoint (``/metrics``,
  ``/healthz``, ``/goodput``, ``/traces/<id>``, ``/flight``,
  ``/alerts``, ``/slo``, ``/signals``),
  fleet-aggregated via the Router (``scripts/ops_server.py`` CLI).
* ``Profiler`` — the paddle.profiler front end: scheduler state machine,
  ``on_trace_ready`` handlers (``export_chrome_tracing``), ``summary()``,
  and ``timer_only=True`` step benchmarking (ips + reader/batch cost split).
* The ``FLAGS_check_nan_inf`` guard lives in the jit train step (it traces
  finite-ness checks into the XLA program); see jit.CompiledTrainStep.

Device-side (XPlane) tracing via ``jax.profiler`` is started only when a
device target (TPU/GPU) is explicitly requested — host tracing alone never
starts the jax profiler; it only asks whether one is running.
"""

from __future__ import annotations

import os
import time
from enum import Enum

from . import counters  # noqa: F401
from . import devicetime  # noqa: F401
from . import flight  # noqa: F401
from . import goodput  # noqa: F401
from . import host_tracer  # noqa: F401
from . import metrics  # noqa: F401
from . import trace  # noqa: F401
from . import health  # noqa: F401
from .goodput import GoodputLedger  # noqa: F401
from .health import SLO, HealthMonitor, Watchdog  # noqa: F401
from .host_tracer import current_stack, span  # noqa: F401
from .metrics import (Histogram, MetricsLogger, memory_summary,  # noqa: F401
                      prometheus_text)
from .ops import OpsServer  # noqa: F401
from .trace import TraceContext  # noqa: F401


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    CUSTOM_DEVICE = 3
    TPU = 4


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """Periodic profiling schedule (reference: profiler/utils.py
    make_scheduler): ``skip_first`` CLOSED steps, then repeating windows of
    ``closed`` CLOSED + ``ready`` READY + ``record`` RECORD steps, the last
    RECORD step of each window being RECORD_AND_RETURN."""
    if not isinstance(record, int) or record < 1:
        raise ValueError(
            f"record should be a positive integer (>= 1), but got {record}: "
            "each profiling window needs at least one RECORD step to return "
            "a trace")
    for arg_name, v in (("closed", closed), ("ready", ready),
                        ("repeat", repeat), ("skip_first", skip_first)):
        if not isinstance(v, int) or v < 0:
            raise ValueError(
                f"{arg_name} should be a non-negative integer, but got {v}")

    def scheduler(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        period = closed + ready + record
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready handler: write the collected host trace as
    chrome://tracing JSON into ``dir_name`` (reference: profiler.py
    export_chrome_tracing → ChromeTracingLogger)."""
    def handle(prof):
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}.pt.trace.json")
        prof.export(path)
        prof._export_dir = dir_name
        prof._chrome_trace_path = path
        return path
    return handle


class _StepTimer:
    """timer_only benchmarking: per-step wall latency, ips, and the
    reader-vs-batch cost split (reader cost = movement of the io.* wait
    counters during the step, i.e. time the step spent blocked on data).

    Under fused multi-step dispatch (jit.CompiledTrainStep
    ``fused_steps=K``) call ``prof.step()`` once per window: one "step" is
    then one K-step XLA launch, so batch_cost / ips are per-window —
    divide/multiply by K for per-training-step numbers."""

    _READER_KEYS = ("io.reader_ns", "io.prefetch_stall_ns",
                    "io.queue_wait_ns")

    def __init__(self):
        self._t_last = None
        self._reader_mark = 0.0
        self._window = []          # (step_s, reader_s, num_samples)

    def _reader_ns(self):
        return float(sum(counters.get(k) for k in self._READER_KEYS))

    def begin(self):
        self._t_last = time.perf_counter()
        self._reader_mark = self._reader_ns()

    def step(self, num_samples=None):
        if self._t_last is None:
            self.begin()
            return
        now = time.perf_counter()
        r_now = self._reader_ns()
        self._window.append((now - self._t_last,
                             (r_now - self._reader_mark) / 1e9, num_samples))
        self._t_last = now
        self._reader_mark = r_now

    def step_info(self, unit=None) -> str:
        if not self._window:
            return "(no steps recorded)"
        n = len(self._window)
        batch = sum(w[0] for w in self._window) / n
        reader = sum(w[1] for w in self._window) / n
        samples = [w[2] for w in self._window if w[2] is not None]
        total_t = sum(w[0] for w in self._window)
        if samples and total_t > 0:
            ips = sum(samples) / total_t
            ips_unit = unit or "samples/s"
        elif total_t > 0:
            ips = n / total_t
            ips_unit = unit or "steps/s"
        else:
            ips, ips_unit = 0.0, unit or "steps/s"
        self._window = []  # paddle semantics: averages since the last call
        return (f"reader_cost: {reader:.5f} s batch_cost: {batch:.5f} s "
                f"ips: {ips:.3f} {ips_unit}")


_LAST_PROFILER = None


class Profiler:
    """paddle.profiler.Profiler over the host tracer (+ jax.profiler XPlane
    when a device target is requested)."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None, with_flops=False):
        if isinstance(scheduler, (tuple, list)):
            start_b, end_b = scheduler
            if end_b <= start_b or start_b < 0:
                raise ValueError(
                    f"scheduler=(start, end) needs 0 <= start < end, got "
                    f"{scheduler!r}")
            rec = end_b - start_b
            self._scheduler = make_scheduler(closed=max(start_b - 1, 0),
                                             ready=1 if start_b > 0 else 0,
                                             record=rec, repeat=1)
        else:
            self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._targets = list(targets) if targets else [ProfilerTarget.CPU]
        self._dir = "/tmp/paddle_tpu_profile"
        self._device_trace = False
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._events: list = []
        self._timer = _StepTimer()
        self._started = False
        self._handled = False  # on_trace_ready already fired for _events

    # -- collection plumbing -------------------------------------------------
    def _collecting(self):
        return self._state in (ProfilerState.RECORD,
                               ProfilerState.RECORD_AND_RETURN)

    def _enter_state(self, new):
        was = self._collecting()
        self._state = new
        now = self._collecting()
        if now and not was and not self._timer_only:
            host_tracer.start()
        elif was and not now and not self._timer_only:
            self._events.extend(host_tracer.stop())

    def start(self):
        global _LAST_PROFILER
        _LAST_PROFILER = self
        self._started = True
        self._step = 0
        self._events = []
        self._handled = False
        self._timer.begin()
        if not self._timer_only and any(
                t in (ProfilerTarget.TPU, ProfilerTarget.GPU,
                      ProfilerTarget.CUSTOM_DEVICE) for t in self._targets):
            os.makedirs(self._dir, exist_ok=True)
            try:
                import jax
                jax.profiler.start_trace(self._dir)
                self._device_trace = True
            except Exception as e:
                import warnings
                warnings.warn(f"device trace did not start: {e} "
                              "(host tracing continues)", RuntimeWarning,
                              stacklevel=2)
        state = (self._scheduler(0) if self._scheduler is not None
                 else ProfilerState.RECORD)
        self._enter_state(state)

    def stop(self):
        if not self._started:
            return
        was_recording = self._collecting()
        self._enter_state(ProfilerState.CLOSED)
        if self._device_trace:
            import jax
            jax.profiler.stop_trace()
            self._device_trace = False
        self._started = False
        if self._on_trace_ready and (was_recording
                                     or (self._events and not self._handled)):
            self._handled = True
            self._on_trace_ready(self)

    def step(self, num_samples=None):
        """Advance the scheduler one train step (also feeds the timer)."""
        self._timer.step(num_samples)
        self._step += 1
        if self._scheduler is None:
            return
        prev = self._state
        new = self._scheduler(self._step)
        self._enter_state(new)
        if (prev == ProfilerState.RECORD_AND_RETURN
                and self._on_trace_ready is not None):
            self._handled = True
            self._on_trace_ready(self)

    def step_info(self, unit=None):
        return self._timer.step_info(unit)

    # -- results -------------------------------------------------------------
    def _all_events(self):
        evts = list(self._events)
        if self._collecting() and not self._timer_only:
            evts.extend(host_tracer.events())
        return evts

    def summary(self, sorted_by="total", op_detail=True, thread_sep=False,
                time_unit="ms"):
        if self._timer_only:
            return self._timer.step_info()
        if isinstance(sorted_by, Enum):  # paddle SortedKeys compat
            sorted_by = "total"
        return host_tracer.summary(self._all_events(), sorted_by=sorted_by,
                                   time_unit=time_unit)

    def export(self, path, format="json"):
        if format not in (None, "json"):
            raise ValueError(f"unsupported export format {format!r} "
                             "(chrome-trace 'json' only)")
        return host_tracer.export_chrome(path, self._all_events())

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def summary(sorted_by="total", time_unit="ms"):
    """Stats table for the most recent Profiler session (module-level
    convenience; falls back to the live host-tracer session)."""
    if _LAST_PROFILER is not None:
        return _LAST_PROFILER.summary(sorted_by=sorted_by,
                                      time_unit=time_unit)
    return host_tracer.summary(sorted_by=sorted_by, time_unit=time_unit)


class RecordEvent:
    """User-facing host trace span (reference: platform/profiler
    RecordEvent): ``host_tracer.span`` with explicit ``begin``/``end``."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._span = None

    def begin(self):
        self._span = span(self.name)
        self._span.__enter__()

    def end(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def load_profiler_result(path):
    """Load an exported chrome-trace JSON back as a dict."""
    import json
    with open(path) as f:
        return json.load(f)
