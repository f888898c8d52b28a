"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

``jax.profiler.ProfileData`` gives planes, their lines, and events with a
start and a duration in nanoseconds on one clock.  On a TPU (looked at by
hand on a real trace, PR 24):

* device planes are named ``/device:TPU:<n>``; their line ``XLA Modules``
  has one event per launch of a compiled program (``jit_decode(<id>)``),
  and ``XLA Ops`` one event per HLO op that ran, nested where an op (a
  ``while``) contains others, and named by its whole HLO instruction (a
  Pallas kernel is a ``custom-call`` whose text has ``tpu_custom_call``);
  ``Steps`` and ``Async XLA Ops`` (copies in flight beside the ops) are
  not read;
* the host plane ``/host:CPU`` has one line per thread, and the spans the
  benchmark writes with ``jax.profiler.TraceAnnotation`` carry the names it
  gave them (``bench.*``).

``reduce`` works on anything with that shape (``.planes`` -> ``.lines`` ->
``.events`` with ``.name``, ``.start_ns``, ``.duration_ns``, ``.stats``),
which is how ``tests/test_trace_reduce.py`` checks it on a synthetic trace.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _span(ev):
    s = float(ev.start_ns)
    return s, s + float(ev.duration_ns)


def _stats(ev):
    try:
        return {str(k): v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def op_name(event_name):
    """An op event's name is the whole HLO instruction on a TPU
    (``%fusion.3 = bf16[..] fusion(..)``): keep what is left of `` = ``,
    and mark a Mosaic kernel, whose instruction is a ``tpu_custom_call``."""
    short = event_name.split(" = ", 1)[0]
    return short + "[mosaic]" if "tpu_custom_call" in event_name else short


def program_name(module_event_name):
    """``jit_decode(1234)`` -> ``jit_decode``."""
    return re.sub(r"\(\d+\)$", "", module_event_name)


def merge(intervals):
    """Union of ``(start, end)`` intervals, as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events):
    """``[(event, self_ns)]``: each event's duration minus the part its
    direct children (events nested inside it on the same line) cover."""
    order = sorted(events, key=lambda ev: (_span(ev)[0], -_span(ev)[1]))
    out, stack = [], []          # stack of [event, end, child_ns]

    def close(until):
        while stack and stack[-1][1] <= until:
            ev, end, child = stack.pop()
            out.append((ev, max(float(ev.duration_ns) - child, 0.0)))

    for ev in order:
        s, e = _span(ev)
        close(s)
        if stack:
            stack[-1][2] += e - s
        stack.append([ev, e, 0.0])
    close(float("inf"))
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce(profile):
    """The reduction of one traced window.  Returns a dictionary:

    ``window_s``   length of the ``bench.window`` span;
    ``busy_s``     seconds in which an op ran on the device, inside the
                   window (union of op intervals, mean over device planes);
    ``n_devices``  device planes that ran an op;
    ``programs``   ``{program: [seconds of each launch that began inside
                   the window]}``;
    ``ops``        ``{(program, op): self seconds}`` inside the window;
    ``labels``     ``{op: its whole HLO instruction}``, for readers that
                   look for a kernel;
    ``gaps``       ``[[name, seconds]]`` idle time of device plane 0, most
                   first: between launches by the ``bench.*`` host span
                   that covers most of the gap (or "unattributed"), inside
                   a launch as ``inside <program>``;
    ``host``       ``{span name: [seconds of each occurrence]}``.
    """
    host, window = {}, None
    host_spans = []
    devices = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(HOST_PREFIX):
                    continue
                s, e = _span(ev)
                if ev.name == WINDOW_SPAN:
                    window = (s, e)
                else:
                    host_spans.append((s, e, ev.name))
                    host.setdefault(ev.name, []).append((e - s) / 1e9)
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    lo, hi = window
    devices.sort(key=lambda p: p.name)

    busy, programs, ops, labels, gaps = [], {}, {}, {}, {}
    for d, plane in enumerate(devices):
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        modules = sorted((_span(ev) + (program_name(ev.name),)
                          for ev in lines.get(MODULES_LINE, ())))
        for s, e, name in modules:
            if lo <= s < hi:
                programs.setdefault(name, []).append((e - s) / 1e9)

        starts = [m[0] for m in modules]

        def program_at(t, _m=modules, _s=starts):
            i = bisect.bisect_right(_s, t) - 1
            return _m[i][2] if i >= 0 and t < _m[i][1] else "?"

        inside = []
        for ev, self_ns in self_times(lines.get(OPS_LINE, ())):
            s, e = _span(ev)
            cs, ce = _clip(s, e, lo, hi)
            if ce <= cs:
                continue
            inside.append((cs, ce))
            share = (ce - cs) / (e - s) if e > s else 0.0
            name = op_name(ev.name)
            key = (program_at(s), name)
            ops[key] = ops.get(key, 0.0) + self_ns * share / 1e9
            labels.setdefault(name, ev.name)
        if not inside:
            continue
        merged = merge(inside)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if d == 0:
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            for gs, ge in zip(edges[0::2], edges[1::2]):
                if ge - gs <= 0:
                    continue
                inside_of = program_at((gs + ge) / 2.0)
                best, best_ov = "unattributed", 0.0
                if inside_of != "?":      # a bubble inside a launch
                    best = f"inside {inside_of}"
                else:
                    for s, e, name in host_spans:
                        ov = min(e, ge) - max(s, gs)
                        if ov > best_ov:
                            best, best_ov = name, ov
                gaps[best] = gaps.get(best, 0.0) + (ge - gs) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "n_devices": len(busy),
        "programs": programs,
        "ops": ops,
        "labels": labels,
        "gaps": sorted(([k, v] for k, v in gaps.items()),
                       key=lambda kv: -kv[1]),
        "host": host,
    }


def breakdown(red, top=10):
    """The ``breakdown`` object of a ``--trace 1`` result line."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[f"{prog}/{op}", s] for (prog, op), s in ops],
            "idle_gaps": [list(g) for g in red["gaps"][:top]]}


def op_seconds(red, pattern):
    """Self seconds of the ops whose label matches ``pattern``, and how
    many distinct ops matched."""
    rx = re.compile(pattern)
    names = {op for op, text in red["labels"].items() if rx.search(text)}
    return (sum(s for (_, op), s in red["ops"].items() if op in names),
            len(names))


def describe(profile, top=25):
    """A trace by hand: every plane and line with its number of events,
    and for device lines the event names that took most time, each with
    the stats the trace keeps on it."""
    out = []
    for plane in profile.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            if not events:
                continue
            total, first = {}, {}
            for ev in events:
                total[ev.name] = total.get(ev.name, 0.0) + float(
                    ev.duration_ns)
                first.setdefault(ev.name, ev)
            show = top if DEVICE_PLANE.match(plane.name) else 8
            for name, ns in sorted(total.items(),
                                   key=lambda kv: -kv[1])[:show]:
                ev = first[name]
                out.append(f"    {ns / 1e6:10.3f} ms  {name[:90]!r} start="
                           f"{ev.start_ns} stats={str(_stats(ev))[:300]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(load(find_xplane(sys.argv[1]))))
