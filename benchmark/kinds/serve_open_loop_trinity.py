"""Kind ``serve_open_loop_trinity``: ``serve_open_loop``'s loop over an
``afmoe`` model (window layers beside a full one, routed experts chosen by
sigmoid scores with a bias).

The timed path, the schedule, the stamps, the end-to-end metrics, the
sampling of requests for the check, the warm-up and the disposal are
``serve_open_loop``'s own, and the routing probe and the numbers of the
check are ``serve_open_loop_deepseek_v2``'s, imported and not copied.  What
is this family's: ``build`` (the model of ``paddle_tpu/models/trinity.py``
handed the weights of ``trinity_weights.py`` through its loader, so nothing
is drawn twice), the chunks each step prefilled (``obs["steps"][i]
["chunks"]``, which the step's share of the peak counts keys by, since a
window layer's token reads ``min(position + 1, W)`` of them), and the
reference: ``reference/trinity.py``, handed one layer's float32 weights at
a time and its routed experts one at a time.

``correct`` compares ``token_gap_mean`` and ``route_disagreement`` (as the
``deepseek_v2`` kind defines them) over the window's longest finished
request and two more, one of them past twice the window.  The 99th
percentile of the gaps is printed and not compared: rounding flips a router
choice for about one token in a hundred, and with it a whole expert's
output in that token's residual stream (the ``deepseek_v2`` kind's
finding), so the percentile falls either side of that population from
seed to seed and read 0.024 to 0.34 for the program where the 8-bit
control read 0.54 (``PERF.md``).  ``calibrate`` reads them
for the program and for two controls: the reference computed in 8-bit
floats (``control_fp8``), and the reference whose window layers attend the
whole prefix (``control_window``, what a program that ignored the window
would serve).  A cell's file takes the keys ``serve_open_loop`` takes.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmark import compare, harness
from benchmark import trinity_weights as tw
from benchmark.kinds.serve_open_loop import (_finished, dispose, end_to_end,
                                             sample, warm_up, window)
from benchmark.kinds.serve_open_loop_deepseek_v2 import (
    _counts, _moe_since, gap_numbers, placed, route_numbers, route_probe)
from benchmark.reference import trinity as ref

#: the numbers that decide ``correct``; the others are shown beside them
COMPARED = ("token_gap_mean", "route_disagreement")


def build(cell, cfg, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models.trinity import TrinityConfig, TrinityForCausalLM
    from paddle_tpu.serving import LLMEngine
    paddle.seed(int(seed) & 0x7FFFFFFF)
    width, first, held = tw.share(cfg)
    dt = cfg["compute_dtype"]
    model = TrinityForCausalLM(
        TrinityConfig.from_hf(cfg, experts_held=(first, held),
                              num_experts=width,
                              initializer_range=cfg["initializer_range"],
                              dtype=dt),
        tensors=lambda name: tw.program_tensor(cfg, seed, name, dt))
    model.eval()
    return LLMEngine(model, **cell["engine"])


def place_chunks(obs, chunk):
    """Each step's prefill chunks, ``(start, tokens)``, as
    ``_Loop.place_prefill`` places them: the last on the step that gave
    the request's first token, the others on the steps before it."""
    for s in obs["steps"]:
        s["chunks"] = []
    for r in obs["requests"]:
        if r["first_step"] is None:
            continue
        T = len(r["prompt"])
        starts = list(range(0, T, chunk))
        for j, start in enumerate(starts):
            at = r["first_step"] - (len(starts) - 1 - j)
            if 0 <= at < len(obs["steps"]):
                obs["steps"][at]["chunks"].append(
                    (start, min(chunk, T - start)))


#: the width the reference pads every checked request but a long one to
_SHORT = 16384


def _width(cell, need, longest):
    """The length the reference pads a request of ``need`` positions (the
    head's rows end there) to: ``_SHORT`` where that holds it, else the
    least multiple of 8192 that holds the ``longest`` request checked, or
    the cell's longest; so a run compiles the reference for two widths,
    and the same two in every run of a cell whose schedule is fixed."""
    n_rows = int(cell["output"]["max"])
    most = -(-(int(cell["prompt"]["max"]) - 1 + n_rows) // n_rows) * n_rows
    w = _SHORT if need <= _SHORT else -(-max(need, longest) // 8192) * 8192
    return min(w, max(most, need))


def _held_table(cfg, chosen, a, b):
    """``[expert layers, held]`` from a run's choices ``[expert layers, T,
    k]`` over the tokens ``a .. b - 1``."""
    _, first, held = tw.share(cfg)
    c = chosen[:, a:b]
    return (c[..., None] == first + np.arange(held)).sum((1, 2))


def reference_side(picked, probe, cell, cfg, seed, control=False):
    """``{"program": numbers}``: how far every served token's reference
    logit lies below the reference's best, and the probe's steps against
    the reference's routers; with ``control`` also ``"control_fp8"`` (the
    8-bit-float reference) and ``"control_window"`` (the reference whose
    window layers see the whole prefix), each against the float32
    reference in the same numbers."""
    wide = lambda tree: {n: x.astype(jnp.float32)              # noqa: E731
                         for n, x in tree.items()}
    dt = cfg["compute_dtype"]
    top = wide(tw.top(cfg, seed, dt))
    layer = lambda l: wide(tw.layer(cfg, seed, l, dt))         # noqa: E731
    expert = lambda l, e: wide(tw.expert(cfg, seed, l, e, dt))  # noqa: E731
    _, first, held = tw.share(cfg)
    n_rows = int(cell["output"]["max"])
    longest = max(len(r["prompt"]) for r in picked) - 1 + n_rows
    lows = ("fp8", "window") if control else ()
    gaps = {k: [] for k in ("f32",) + lows}
    routed = {k: [] for k in ("f32",) + lows}
    for i, r in enumerate(picked):
        served = np.asarray(r["tokens"], np.int32)
        T, n = len(r["prompt"]), len(served)

        def run(tokens, low=None):
            ids = np.concatenate([r["prompt"], tokens[:-1]])
            ids = np.pad(ids, (0, _width(cell, T - 1 + n_rows, longest)
                               - len(ids)))
            out, chosen = ref.logits_rows(
                top, layer, expert, cfg, (first, held), ids,
                jnp.int32(T - 1), n_rows, "fp8" if low == "fp8" else "f32",
                window=low != "window")
            return np.asarray(out)[:n], np.asarray(chosen)

        out, chosen = run(served)
        gaps["f32"].append(compare.token_gaps(out, served))
        # the probe's tokens are the window's unless rounding fell
        # otherwise in another batch: then its own sequence is routed
        again = np.asarray(probe["tokens"][i], np.int32)
        routed["f32"].append(chosen if np.array_equal(again, served)
                             else run(again)[1])
        for low in lows:
            low_out, low_chosen = run(served, low)
            gaps[low].append(compare.token_gaps(out, low_out.argmax(-1)))
            routed[low].append(low_chosen)
    spans, chunked = placed(probe, picked, cell["engine"]["prefill_chunk"])
    table = lambda chosen, span: sum(                          # noqa: E731
        _held_table(cfg, chosen[i], a, b) for i, a, b in span)
    steps = list(zip(probe["steps"], spans, chunked))
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros(0)  # noqa: E731
    out = {"program": {**gap_numbers(cat(gaps["f32"])), **route_numbers(
        [(s["per_expert"], table(routed["f32"], span), c)
         for s, span, c in steps if span])}}
    for low in lows:
        out["control_" + low] = {**gap_numbers(cat(gaps[low])),
                                 **route_numbers(
            [(table(routed[low], span), table(routed["f32"], span), c)
             for s, span, c in steps if span])}
    return out


def check(obs, cell, cfg, seed):
    """The compared numbers over the sampled requests; every one infinite
    (so not ``correct``) where none was sampled, or where the longest
    prompt checked does not pass twice the window, so that no check
    passes without a row's ring having wrapped past its end."""
    picked = obs["picked"]
    longest = max((len(r["prompt"]) for r in picked), default=0)
    if longest <= 2 * cfg["sliding_window"]:
        return ({k: float("inf") for k in COMPARED},
                {"checked_requests": len(picked), "longest_checked": longest})
    got = reference_side(picked, obs["route_probe"], cell, cfg,
                         seed)["program"]
    numbers = {k: float("inf") if got[k] is None else got[k]
               for k in COMPARED}
    return numbers, {
        "checked_requests": len(picked),
        "longest_checked": longest,
        "probe_steps": len(obs["route_probe"]["steps"]),
        **{k: v for k, v in got.items() if k not in COMPARED}}


def serve(engine, cell, cfg, seed, seconds, trace, keep_trace=None):
    """Warm-up, the window, the expert layers' counts over it, each step's
    chunks, and the routing probe: ``obs``."""
    warm_up(engine, cell, cfg, seed)
    before = _counts(engine)
    obs = window(engine, cell, cfg, seed, seconds, trace, keep_trace)
    obs["moe"] = _moe_since(engine, before)
    obs["memory_peak_bytes"] = harness.memory_peak_bytes()
    place_chunks(obs, engine.prefill_chunk)
    obs["picked"] = sample(obs, cell, seed)
    obs["route_probe"] = route_probe(engine, obs["picked"])
    return obs


def calibrate(cell, cfg, seed, seconds, control):
    """The readings a limit is set from, for one seed (see
    ``serve_open_loop.calibrate``)."""
    engine = build(cell, cfg, seed)
    obs = serve(engine, cell, cfg, seed, seconds, False)
    dispose(engine)
    del engine
    return {**(reference_side(obs["picked"], obs["route_probe"], cell, cfg,
                              seed, control) if obs["picked"] else {}),
            "checked_requests": len(obs["picked"]),
            "longest_checked": max((len(r["prompt"]) for r in obs["picked"]),
                                   default=0),
            "finished": sum(_finished(r) for r in obs["requests"]),
            "offered": len(obs["requests"]),
            "memory_peak_bytes": obs["memory_peak_bytes"]}


def sweep(cell, cfg, seed, seconds, rates):
    """The sweep that finds the knee (``serve_open_loop_deepseek_v2.sweep``
    over this family's engine)."""
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
