"""Kind ``serve_open_loop_olmo_hybrid``: ``serve_open_loop``'s loop over an
``olmo_hybrid`` model.

The timed path, the schedule, the stamps, the end-to-end metrics, the
sampling of requests for the check, the warm-up and the disposal are
``serve_open_loop``'s own, imported and not copied.  What is this
family's: ``build`` (the model of ``paddle_tpu/models/olmo_hybrid.py``
with the weights of ``olmo_hybrid_weights.py``, installed one tensor at a
time, each in place of the constructor's: a second full set of 8.2 GB
would not fit) and
``check`` (the served tokens' logits under ``reference/olmo_hybrid.py``,
which is handed one layer's float32 weights at a time).

A cell's file takes the keys ``serve_open_loop`` takes.  A configuration's
file holds the published ``config.json`` keys as they are, and
``initializer_range``, ``compute_dtype``.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmark import compare, harness
from benchmark import olmo_hybrid_weights as hweights
from benchmark.kinds.serve_open_loop import (_finished, dispose,
                                             end_to_end, sample, warm_up,
                                             window)
from benchmark.reference import olmo_hybrid as ref


def build(cell, cfg, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                               OlmoHybridForCausalLM)
    from paddle_tpu.serving import LLMEngine
    paddle.seed(int(seed) & 0x7FFFFFFF)
    model = OlmoHybridForCausalLM(OlmoHybridConfig.from_hf(
        cfg, initializer_range=cfg["initializer_range"],
        dtype=cfg["compute_dtype"]))
    named = dict(model.named_parameters())
    if set(named) != set(hweights.PROGRAM_TENSORS):
        raise ValueError("the model's parameters are not the benchmark's: "
                         f"{sorted(set(named) ^ set(hweights.PROGRAM_TENSORS))}")
    for name, p in named.items():
        # the constructor's draw goes before the benchmark's is made: with
        # both alive the largest tensor's turn peaked at 15.1 GB of 16.9
        shape, dtype = tuple(p.shape), p._data.dtype
        p._data = None
        made = hweights.program_tensor(cfg, seed, name, cfg["compute_dtype"])
        if shape != made.shape or dtype != made.dtype:
            raise ValueError(f"{name}: {shape} {dtype} in the program, "
                             f"{made.shape} {made.dtype} in the benchmark")
        p._data = made
    model.eval()
    return LLMEngine(model, **cell["engine"])


def reference_gaps(picked, cell, cfg, seed, control=False):
    """As ``serve_open_loop.reference_gaps``: for every served token of the
    sampled requests, how far its reference logit lies below the
    reference's best; with ``control`` also the gap of the token the
    8-bit-float reference puts first at each position."""
    wide = lambda tree: {n: x.astype(jnp.float32)              # noqa: E731
                         for n, x in tree.items()}
    top = wide(hweights.top(cfg, seed, cfg["compute_dtype"]))
    layer = lambda l: wide(hweights.layer(cfg, seed, l,        # noqa: E731
                                          cfg["compute_dtype"]))
    n_rows = int(cell["output"]["max"])
    # every sequence is padded to the ONE width the cell's longest request
    # needs (causal: what follows a position changes nothing before it),
    # so the reference compiles once per kind of layer whatever the seed
    # picks: a compile is 15 s, a layer over 16,896 positions 0.4 s
    width = -(-(int(cell["prompt"]["max"]) - 1 + n_rows) // n_rows) * n_rows
    gaps, control_gaps = [], []
    for r in picked:
        served = np.asarray(r["tokens"], np.int32)
        T, n = len(r["prompt"]), len(served)
        ids = np.concatenate([r["prompt"], served[:-1]])
        ids = np.pad(ids, (0, width - len(ids)))
        first = jnp.int32(T - 1)
        out = np.asarray(ref.logits_rows(top, layer, cfg, ids, first,
                                         n_rows, "f32"))[:n]
        gaps.append(compare.token_gaps(out, served))
        if control:
            low = np.asarray(ref.logits_rows(top, layer, cfg, ids, first,
                                             n_rows, "fp8"))[:n]
            control_gaps.append(compare.token_gaps(out, low.argmax(-1)))
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros(0)  # noqa: E731
    return cat(gaps), cat(control_gaps)


def check(obs, cell, cfg, seed):
    picked = sample(obs, cell, seed)
    gaps, _ = reference_gaps(picked, cell, cfg, seed)
    worst = float(gaps.max()) if len(gaps) else float("inf")
    return ({"token_gap": worst},
            {"checked_requests": len(picked), "checked_tokens": len(gaps),
             "longest_checked": max((len(r["prompt"]) for r in picked),
                                    default=0)})


def calibrate(cell, cfg, seed, seconds, control):
    """The readings a limit is set from, for one seed (see
    ``serve_open_loop.calibrate``)."""
    engine = build(cell, cfg, seed)
    warm_up(engine, cell, cfg, seed)
    obs = window(engine, cell, cfg, seed, seconds, False)
    dispose(engine)
    del engine
    picked = sample(obs, cell, seed)
    gaps, low = reference_gaps(picked, cell, cfg, seed, control)
    out = {"program": {"token_gap": float(gaps.max())},
           "checked_tokens": len(gaps), "checked_requests": len(picked),
           "nonzero_gaps": int((gaps > 0).sum()),
           "finished": sum(_finished(r) for r in obs["requests"]),
           "offered": len(obs["requests"])}
    if control:
        out["control_fp8"] = {"token_gap": float(low.max())}
        out["control_nonzero_gaps"] = int((low > 0).sum())
    return out


def sweep(cell, cfg, seed, seconds, rates):
    """The sweep that finds the knee (see ``serve_open_loop.sweep``): one
    engine, each rate offered for ``seconds`` and then drained."""
    engine = build(cell, cfg, seed)
    warm_up(engine, cell, cfg, seed)
    for i, rate in enumerate(rates):
        obs = window(engine, dict(cell, rate_per_s=rate), cfg, seed + i,
                     seconds, False)
        reqs = obs["requests"]
        done_at = [r["token_s"][-1] if _finished(r) else float("inf")
                   for r in reqs]
        backlog = [sum(r["due_s"] <= t < d for r, d in zip(reqs, done_at))
                   for t in (seconds * q / 4 for q in (1, 2, 3, 4))]
        yield {"rate_per_s": rate, "offered": len(reqs),
               "finished": sum(map(_finished, reqs)),
               "backlog_at_quarters": backlog,
               "drained_s": obs["drained_s"], **end_to_end(obs),
               "step_p50_ms": 1e3 * float(np.median(
                   [s["end"] - s["start"] for s in obs["steps"]])),
               "retraces": obs["retraces"]}
    dispose(engine)


def run(ctx):
    cell, cfg, seed = ctx["cell"], ctx["config"], ctx["seed"]
    engine = build(cell, cfg, seed)
    warm_up(engine, cell, cfg, seed)
    obs = window(engine, cell, cfg, seed, ctx["seconds"], ctx["trace"],
                 ctx.get("keep_trace"))
    peak = harness.memory_peak_bytes()
    dispose(engine)
    del engine
    t_check = time.perf_counter()
    numbers, where = check(obs, cell, cfg, seed)
    obs["check_s"] = time.perf_counter() - t_check
    reqs = obs["requests"]
    return {
        "attempted": len(reqs),
        "failed": sum(not _finished(r) for r in reqs),
        "end_to_end": end_to_end(obs),
        "numbers": numbers, "where": where, "obs": obs,
        "memory_peak_bytes": peak, "t_window_start": obs["t_start"],
    }
