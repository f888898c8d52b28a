#!/usr/bin/env python3
"""Read, on the chip, what a cell's limits are set from.

    python3 benchmark/calibrate.py --workload <cell> --seeds 11,12,13 \\
        [--control-seeds 11,12,13] [--seconds 15] [--out FILE]
    python3 benchmark/calibrate.py --workload <cell> --seeds 11 \
        --sweep 1.5,2,2.5,3 --seconds 30

For each seed, in one process (set-up is paid once per seed, compilation
once): the program's numbers against the reference (the LOWER reading is
their largest), and for the control seeds the numbers of the reference
computed in 8-bit floats and of each planted fault (the UPPER reading is their
smallest).  One JSON line per seed, on standard output and appended to
``--out``.  Benchmark runs never call this; ``PERF.md`` records what it
read and the limit set between the readings.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None):
    from benchmark import run as bench_run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sweep", default="",
                    help="serving cells: offer each of these rates for "
                    "--seconds on the first seed, to find the knee")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]
    bench = bench_run._load(os.path.join(bench_run.ROOT, "BENCHMARK.json"))
    cell, cfg = bench_run.load_cell(bench, args.workload, args.rehearse)
    kind = bench_run._module("kinds", cell["kind"])
    bench_run.check_device(cell, args.rehearse)
    if not args.rehearse:
        from paddle_tpu.core import compile_cache
        compile_cache.enable()
    control = set(ints(args.control_seeds))
    seeds = ints(args.seeds)

    def lines():
        if args.sweep:
            rates = [float(x) for x in args.sweep.split(",")]
            for line in kind.sweep(cell, cfg, seeds[0], args.seconds, rates):
                yield {"workload": args.workload, "seed": seeds[0], **line}
            return
        for seed in seeds:
            t0 = time.perf_counter()
            yield {"workload": args.workload, "seed": seed,
                   **kind.calibrate(cell, cfg, seed, args.seconds,
                                    seed in control),
                   "seconds": time.perf_counter() - t0}

    for line in lines():
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
