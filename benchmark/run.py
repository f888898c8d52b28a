#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell's file ``benchmark/workloads/<cell>.json``, its
configuration's file, the runner ``benchmark/kinds/<kind>.py`` of the
cell's ``kind``, and one reader ``benchmark/layer_metrics/<metric>.py``
(or ``<family>.py`` for a dotted ``family.member``) per per-layer metric.
A name that cannot be found is an error.  See ``benchmark/README.md``.

The last line on standard output is the result.  Without a TPU whose
``device_kind`` is in ``benchmark/peaks.json``, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result; there is
no CPU option on the measuring path.  ``--rehearse`` is the builder's own
dry run on the CPU at the tiny sizes under each file's ``rehearse`` key:
its line says ``"rehearsal": true`` and names the device it ran on, and no
number in it is a measurement.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()    # process start, as near as Python lets us see

import argparse                      # noqa: E402
import importlib                     # noqa: E402
import json                          # noqa: E402
import math                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _entry(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SystemExit(f"run.py: BENCHMARK.json has no {what} {name!r}")
    return found[0]


def _module(package, name):
    """``benchmark/<package>/<name>.py``; for a dotted metric also its
    family's ``<family>.py``."""
    for candidate in (name, name.split(".")[0]):
        path = os.path.join(HERE, package, candidate + ".py")
        if os.path.isfile(path):
            return importlib.import_module(
                f"benchmark.{package}.{candidate}")
    raise SystemExit(f"run.py: no benchmark/{package}/{name}.py")


def load_cell(bench, workload, rehearse):
    """The cell's and its configuration's files, with the ``rehearse``
    overrides applied where asked."""
    entry = _entry(bench["workloads"], workload, "workload")
    cell = _load(os.path.join(HERE, "workloads", workload + ".json"))
    centry = _entry(bench["configs"], entry["config"], "configuration")
    cfg = _load(os.path.join(ROOT, centry["file"]))
    for side in (cell, cfg):
        over = side.pop("rehearse", {})
        if rehearse:
            side.update(over)
    if cell["config"] != entry["config"] or cell["chips"] != entry["chips"]:
        raise SystemExit(f"run.py: {workload}.json and BENCHMARK.json "
                         "disagree on the cell's configuration or chips")
    return cell, cfg


def metrics_of(bench, workload, group):
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if workload in m.get("workloads", [workload])]


def check_device(cell, rehearse):
    import jax
    dev = jax.devices()[0]
    peaks = _load(os.path.join(HERE, "peaks.json"))
    if rehearse:
        return dev, next(iter(peaks.values()))
    if dev.platform != "tpu":
        raise SystemExit(f"run.py: needs a TPU, found platform "
                         f"{dev.platform!r}; there is no CPU mode")
    if dev.device_kind not in peaks:
        raise SystemExit(f"run.py: no published peak for device_kind "
                         f"{dev.device_kind!r} in benchmark/peaks.json")
    if jax.device_count() < cell["chips"]:
        raise SystemExit(f"run.py: the cell needs {cell['chips']} chips, "
                         f"found {jax.device_count()}")
    return dev, peaks[dev.device_kind]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dry run at tiny sizes; measures nothing")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="leave the profiler's trace of a --trace 1 run "
                    "in DIR, to look at it with trace_reduce.py")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg = load_cell(bench, args.workload, args.rehearse)
    kind = _module("kinds", cell["kind"])
    dev, peak = check_device(cell, args.rehearse)

    import jax
    from paddle_tpu.core import compile_cache
    if not args.rehearse:
        compile_cache.enable()
    from benchmark import compare, trace_reduce

    out = kind.run({"cell": cell, "config": cfg, "seed": args.seed,
                    "seconds": args.seconds, "trace": bool(args.trace),
                    "keep_trace": args.keep_trace})

    values = dict(out["end_to_end"])
    values["setup_s"] = out["t_window_start"] - _T0
    group = "per_layer" if args.trace else "end_to_end"
    metrics, notes = {}, {}
    for m in metrics_of(bench, args.workload, group):
        if args.trace:
            value = _module("layer_metrics", m["name"]).read(
                m["name"], out["obs"], cell, cfg, peak)
        else:
            value = values.get(m["name"])
        if isinstance(value, tuple):      # (value, what bounds it)
            value, notes[m["name"]] = value
        if value is None:
            if not args.trace:
                raise SystemExit(f"run.py: kind {cell['kind']!r} did not "
                                 f"report {m['name']!r}")
            continue
        if not math.isfinite(value):
            raise SystemExit(f"run.py: {m['name']} is {value!r}")
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if args.trace:
        red = out["obs"]["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = trace_reduce.breakdown(red)
        result["notes"] = notes
    if args.rehearse:
        result["rehearsal"] = True
    result["check_s"] = out["obs"].get("check_s")
    correct, compared = compare.judge(out["numbers"], cell["limits"])
    result = {"correct": correct, **result, "compared": compared}
    compare.print_compared(compared, out["where"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
