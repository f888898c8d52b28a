"""Kind ``serve_open_loop_jamba``: ``serve_open_loop``'s loop over a
``jamba`` model (Mamba-1 layers beside attention layers with one K/V head).

The timed path, the schedule, the stamps, the end-to-end metrics, the
sampling of requests for the check, the warm-up and the disposal are
``serve_open_loop``'s own, each step's chunks are placed as
``serve_open_loop_trinity`` places them, and the numbers of the check are
``serve_open_loop_deepseek_v2``'s, imported and not copied.  What is this
family's: ``build`` (the model of ``paddle_tpu/models/jamba.py`` handed the
weights of ``jamba_weights.py`` through its loader, so nothing is drawn
twice) and the reference: ``reference/jamba.py``, handed one layer's
float32 weights at a time.

``correct`` compares ``token_gap_mean`` and ``token_gap_p99``: for every
token the window served to the checked requests (the longest finished and
others, up to ``check_min_tokens``), how far its logit under the float32
reference lies below the reference's best at that position, the prompt's
prefill and every decode step before it served through the cache.
``calibrate`` reads the same numbers for the program and for two controls,
each against the float32 reference: the reference computed in 8-bit floats
(``control_fp8``) and the reference whose scans keep their state in
bfloat16 between tokens (``control_bf16_state``).  A cell's file takes the
keys ``serve_open_loop`` takes.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmark import compare, harness
from benchmark import jamba_weights as jw
from benchmark.kinds.serve_open_loop import (_finished, dispose, end_to_end,
                                             sample, warm_up, window)
from benchmark.kinds.serve_open_loop_deepseek_v2 import gap_numbers
from benchmark.kinds.serve_open_loop_trinity import place_chunks
from benchmark.reference import jamba as ref
from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM

#: the numbers that decide ``correct``; the others are shown beside them
COMPARED = ("token_gap_mean", "token_gap_p99")

#: the controls ``calibrate`` reads: (matrix products, the scan's state)
CONTROLS = {"control_fp8": ("fp8", jnp.float32),
            "control_bf16_state": ("f32", jnp.bfloat16)}


def build(cell, cfg, seed):
    import paddle_tpu as paddle
    from paddle_tpu.serving import LLMEngine
    paddle.seed(int(seed) & 0x7FFFFFFF)
    dt = cfg["compute_dtype"]
    model = JambaForCausalLM(
        JambaConfig.from_hf(cfg, initializer_range=cfg["initializer_range"],
                            dtype=dt),
        tensors=lambda name: jw.program_tensor(cfg, seed, name, dt))
    model.eval()
    return LLMEngine(model, **cell["engine"])


def reference_side(picked, cell, cfg, seed, control=False):
    """``{"program": numbers}``: how far every served token's reference
    logit lies below the reference's best; with ``control`` also each of
    ``CONTROLS``, the gaps of the tokens that control puts first at the
    same positions (read over the same prompts and served tokens)."""
    wide = lambda tree: {n: x.astype(jnp.float32)              # noqa: E731
                         for n, x in tree.items()}
    dt = cfg["compute_dtype"]
    top = wide(jw.top(cfg, seed, dt))
    layer = lambda l: wide(jw.layer(cfg, seed, l, dt))         # noqa: E731
    n_rows = int(cell["output"]["max"])
    # every sequence is padded to the ONE width the cell's longest request
    # needs (causal: what follows a position changes nothing before it),
    # so the reference compiles once per kind of layer whatever is checked
    width = -(-(int(cell["prompt"]["max"]) - 1 + n_rows) // n_rows) * n_rows
    gaps = {k: [] for k in ("program",) + (tuple(CONTROLS) if control
                                           else ())}
    for r in picked:
        served = np.asarray(r["tokens"], np.int32)
        T, n = len(r["prompt"]), len(served)
        ids = np.concatenate([r["prompt"], served[:-1]])
        ids = np.pad(ids, (0, width - len(ids)))

        def run(prec="f32", state=jnp.float32):
            return np.asarray(ref.logits_rows(
                top, layer, cfg, ids, jnp.int32(T - 1), n_rows, prec,
                state))[:n]

        out = run()
        gaps["program"].append(compare.token_gaps(out, served))
        for name, (prec, state) in CONTROLS.items() if control else ():
            gaps[name].append(compare.token_gaps(
                out, run(prec, state).argmax(-1)))
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros(0)  # noqa: E731
    return {k: gap_numbers(cat(v)) for k, v in gaps.items()}


def check(obs, cell, cfg, seed):
    picked = obs["picked"]
    if not picked:
        return {k: float("inf") for k in COMPARED}, {"checked_requests": 0}
    got = reference_side(picked, cell, cfg, seed)["program"]
    numbers = {k: float("inf") if got[k] is None else got[k]
               for k in COMPARED}
    return numbers, {
        "checked_requests": len(picked),
        "longest_checked": max(len(r["prompt"]) for r in picked),
        **{k: v for k, v in got.items() if k not in COMPARED}}


def serve(engine, cell, cfg, seed, seconds, trace, keep_trace=None):
    """Warm-up, the window, each step's chunks and the requests to check:
    ``obs``."""
    warm_up(engine, cell, cfg, seed)
    obs = window(engine, cell, cfg, seed, seconds, trace, keep_trace)
    obs["memory_peak_bytes"] = harness.memory_peak_bytes()
    place_chunks(obs, engine.prefill_chunk)
    obs["picked"] = sample(obs, cell, seed)
    return obs


def calibrate(cell, cfg, seed, seconds, control):
    """The readings a limit is set from, for one seed (see
    ``serve_open_loop.calibrate``)."""
    engine = build(cell, cfg, seed)
    obs = serve(engine, cell, cfg, seed, seconds, False)
    dispose(engine)
    del engine
    return {**(reference_side(obs["picked"], cell, cfg, seed, control)
               if obs["picked"] else {}),
            "checked_requests": len(obs["picked"]),
            "longest_checked": max((len(r["prompt"]) for r in obs["picked"]),
                                   default=0),
            "finished": sum(_finished(r) for r in obs["requests"]),
            "offered": len(obs["requests"]),
            "memory_peak_bytes": obs["memory_peak_bytes"]}


def sweep(cell, cfg, seed, seconds, rates):
    """The sweep that finds the knee (``serve_open_loop.sweep`` over this
    family's engine): one engine, each rate offered for ``seconds`` and
    then drained."""
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
        half = [[(r["token_s"][0] - r["due_s"]) * 1e3 for r in reqs
                 if _finished(r) and lo <= r["due_s"] < hi]
                for lo, hi in ((0, seconds / 2), (seconds / 2, seconds))]
        yield {"rate_per_s": rate, "offered": len(reqs),
               "finished": sum(map(_finished, reqs)),
               "backlog_at_quarters": backlog,
               "ttft_p50_ms_by_half": [compare.percentile(h, 50) if h
                                       else None for h in half],
               "drained_s": obs["drained_s"], **end_to_end(obs),
               "step_p50_ms": 1e3 * float(np.median(
                   [s["end"] - s["start"] for s in obs["steps"]])),
               "retraces": obs["retraces"]}
    dispose(engine)


def run(ctx):
    cell, cfg, seed = ctx["cell"], ctx["config"], ctx["seed"]
    engine = build(cell, cfg, seed)
    obs = serve(engine, cell, cfg, seed, ctx["seconds"], ctx["trace"],
                ctx.get("keep_trace"))
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
        "memory_peak_bytes": obs["memory_peak_bytes"],
        "t_window_start": obs["t_start"],
    }
