"""Kind ``serve_open_loop_deepseek_v2``: ``serve_open_loop``'s loop over a
``deepseek_v2`` model.

The timed path, the schedule, the stamps, the end-to-end metrics, the
sampling of requests for the check, the warm-up and the disposal are
``serve_open_loop``'s own, imported and not copied.  What is this
family's: ``build`` (the model of ``paddle_tpu/models/deepseek_v2.py`` with
the weights of ``deepseek_v2_weights.py``, installed one tensor at a time,
each in place of the constructor's), the expert layers' counts read at the
window's two ends (``obs["moe"]``), and ``check``: the served tokens'
logits under ``reference/deepseek_v2.py``, which is handed one layer's
float32 weights at a time and its routed experts one at a time, plus
``route_disagreement``.

``token_gap_mean`` and ``token_gap_p99``.  The other serving kinds compare
the WIDEST gap of a served token's reference logit below the reference's
best.  Here 7 to 10 served tokens in a hundred are not the float32
reference's first choice, with gaps that fall off exponentially: the
widest of 200-550 read 0.66 to 3.25 over 29 seeds where the 8-bit
control's read 2.96 to 3.97 (``PERF.md``), so no limit lies between the
two readings.  The reference itself shows what the gaps are made of
(``calibrate`` with a control seed): with its products' operands rounded
to bfloat16 it puts another token first at 7 positions in a hundred too
(mean gap 0.012-0.013, half the program's; widest 0.93), and with the
float32 run's routing forced on that bfloat16 run the mean falls
twentyfold and the widest to 0.05-0.07: rounding flips one router choice
in a hundred, and with it a whole expert's output in a token's residual
stream.  The MEAN gap over the checked tokens is compared (the program's
largest and the control's smallest are an order of magnitude apart), and
beside it the 99th percentile, which a fault confined to a few tokens in
a hundred moves and the mean does not; the widest is printed and not
compared, as ``compare.train_numbers`` does with the loss gap.

``route_disagreement`` and ``route_disagreement_decode``.  The timed path
returns tokens, not the routers' choices, so after the window the sampled
requests are served again through the same engine and programs, each
submitted when the one before it has its first token: chunks run beside
decoding rows as in the window, and the last tokens are decoded with all
of them live in a batch of idle rows.  The expert layers' counts are read
after EVERY step, and the reference gives the same table from its own
routers over the tokens that step routed: a chunk's 1,024 for a step that
carried one, one token per decoding row for a step that did not, so that
two tokens that swap experts do not cancel there.  The number is the
share of (token, layer, held expert) assignments that the two tables of a
step do not share, over all steps: ``sum |a - b| / (sum a + sum b)``; the
``_decode`` number is the same over the steps that carried no chunk
(``jit_decode`` alone, its ``running`` mask and its counting sort over a
few rows).  Upper limits' numbers (``compare.judge`` holds upper limits
only): 0 where the program routes as the reference does, and a program
that leaves experts out, routes over other groups or counts idle rows
cannot pass.

A cell's file takes the keys ``serve_open_loop`` takes.  A configuration's
file holds the published ``config.json`` keys as they are, cut to one
chip's share as ``deepseek_v2_weights.share`` reads it, and
``initializer_range``, ``compute_dtype``.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmark import compare, harness
from benchmark import deepseek_v2_weights as dweights
from benchmark.kinds.serve_open_loop import (_finished, dispose,
                                             end_to_end, sample, warm_up,
                                             window)
from benchmark.reference import deepseek_v2 as ref

#: the reference pads a sequence to one of these widths (or the cell's
#: longest), so that it compiles a handful of times whatever the seed picks
_MIN_WIDTH = 4096


def build(cell, cfg, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                               DeepseekV2ForCausalLM)
    from paddle_tpu.serving import LLMEngine
    paddle.seed(int(seed) & 0x7FFFFFFF)
    width, first, held = dweights.share(cfg)
    model = DeepseekV2ForCausalLM(DeepseekV2Config.from_hf(
        cfg, experts_held=(first, held), n_routed_experts=width,
        initializer_range=cfg["initializer_range"],
        dtype=cfg["compute_dtype"]))
    named = dict(model.named_parameters())
    if set(named) != set(dweights.PROGRAM_TENSORS):
        raise ValueError("the model's parameters are not the benchmark's: "
                         f"{sorted(set(named) ^ set(dweights.PROGRAM_TENSORS))}")
    for name, p in named.items():
        # the constructor's draw goes before the benchmark's is made
        shape, dtype = tuple(p.shape), p._data.dtype
        p._data = None
        made = dweights.program_tensor(cfg, seed, name, cfg["compute_dtype"])
        if shape != made.shape or dtype != made.dtype:
            raise ValueError(f"{name}: {shape} {dtype} in the program, "
                             f"{made.shape} {made.dtype} in the benchmark")
        p._data = made
    model.eval()
    return LLMEngine(model, **cell["engine"])


def _counts(engine):
    """The expert layers' counts so far, as the model reads the arrays
    its programs carry on the device (and publishes their counters)."""
    return engine.model.moe_load(engine.step_state())


def _moe_since(engine, before):
    """The expert layers' counts added since ``before`` (a ``_counts``
    reading)."""
    load = _counts(engine)
    return {k: load[k] - before[k]
            for k in ("assignments", "tokens", "per_expert")}


def route_probe(engine, picked):
    """Serve the sampled requests again, each submitted when the one
    before it has its first token, and read the expert layers' counts
    after every step: ``{"tokens": [the tokens each was given], "steps":
    [{"per_expert": [expert layers, held] the step added, "routed": the
    tokens it routed, "events": [(request, index of the token it was
    given)]}]}``."""
    if not picked:
        return None
    # what the window left unfinished (it has failed already) runs out
    # first, so that the counts between two readings are the probe's
    give_up = time.perf_counter() + 120.0
    while engine.has_work() and time.perf_counter() < give_up:
        engine.step()
    handles, steps = [], []
    last = _counts(engine)
    give_up = time.perf_counter() + 300.0
    while time.perf_counter() < give_up:
        if len(handles) < len(picked) and (not handles
                                           or handles[-1].tokens):
            r = picked[len(handles)]
            handles.append(engine.add_request(
                r["prompt"], max_new_tokens=r["max_new_tokens"], seed=0))
        elif not engine.has_work():
            break
        events = engine.step()
        now = _counts(engine)
        place = {id(h): i for i, h in enumerate(handles)}
        steps.append({
            "per_expert": now["per_expert"] - last["per_expert"],
            "routed": now["tokens"] - last["tokens"],
            "events": [(place[id(ev["request"])], ev["index"])
                       for ev in events if ev["type"] == "token"
                       and id(ev["request"]) in place]})
        last = now
    if len(handles) < len(picked) or not all(h.is_finished for h in handles):
        raise RuntimeError("the routing probe did not serve its requests")
    return {"tokens": [[int(t) for t in h.tokens] for h in handles],
            "steps": steps}


def placed(probe, picked, chunk):
    """What each step of the probe routed: ``[[(request, from, to)]]``,
    positions ``from .. to - 1`` of that request, and whether the step
    carried a chunk.  A request's chunks lie one a step on the steps up
    to the one that gave its first token (as ``_Loop.place_prefill`` puts
    them); a later token came from the decode launch of its step, which
    routed the token before it."""
    spans = [[] for _ in probe["steps"]]
    chunked = [False] * len(spans)
    for s, step in enumerate(probe["steps"]):
        for i, index in step["events"]:
            T = len(picked[i]["prompt"])
            if index:
                spans[s].append((i, T + index - 1, T + index))
                continue
            starts = range(0, T, chunk)
            for j, start in enumerate(starts):
                at = s - (len(starts) - 1 - j)
                if at < 0:
                    raise RuntimeError("the routing probe gave a first "
                                       "token before its chunks had steps")
                spans[at].append((i, start, min(start + chunk, T)))
                chunked[at] = True
    for step, span in zip(probe["steps"], spans):
        if step["routed"] != sum(b - a for _, a, b in span):
            raise RuntimeError(
                "the routing probe cannot say which tokens a step routed: "
                f"{step['routed']} by the program's count, {span} by its "
                "events")
    return spans, chunked


def _width(cell, n_ids):
    n_rows = int(cell["output"]["max"])
    most = -(-(int(cell["prompt"]["max"]) - 1 + n_rows) // n_rows) * n_rows
    w = _MIN_WIDTH
    while w < n_ids:
        w *= 2
    return min(w, most) if most >= n_ids else w


def _held_table(cfg, chosen, a, b):
    """``[expert layers, held]`` from a run's choices ``[expert layers,
    T, k]`` over the tokens ``a .. b - 1``."""
    _, first, held = dweights.share(cfg)
    c = chosen[:, a:b]
    return (c[..., None] == first + np.arange(held)).sum((1, 2))


def disagreement(pairs):
    """``sum |a - b| / (sum a + sum b)`` over pairs of tables; nothing
    where there is no pair."""
    pairs = [(np.asarray(a, np.float64), np.asarray(b, np.float64))
             for a, b in pairs]
    if not pairs:
        return None
    return float(sum(np.abs(a - b).sum() for a, b in pairs)
                 / max(sum(a.sum() + b.sum() for a, b in pairs), 1.0))


def route_numbers(tables):
    """``tables``: a pair of tables and whether the step carried a chunk,
    per step."""
    return {"route_disagreement": disagreement(
                [(a, b) for a, b, _ in tables]),
            "route_disagreement_decode": disagreement(
                [(a, b) for a, b, chunked in tables if not chunked])}


def reference_side(picked, probe, cell, cfg, seed, control=False):
    """``{"program": numbers}``: how far every served token's reference
    logit lies below the reference's best, and the probe's steps against
    the reference's routers; with ``control`` also ``"control_fp8"``, the
    8-bit-float reference against the float32 one in the same numbers,
    and ``"reference_bf16"`` / ``"reference_bf16_forced"``, the gaps of
    the reference with its operands rounded to bfloat16, routing by
    itself and as the float32 run did."""
    wide = lambda tree: {n: x.astype(jnp.float32)              # noqa: E731
                         for n, x in tree.items()}
    dt = cfg["compute_dtype"]
    top = wide(dweights.top(cfg, seed, dt))
    layer = lambda l: wide(dweights.layer(cfg, seed, l, dt))   # noqa: E731
    expert = lambda l, e: wide(dweights.expert(cfg, seed, l, e,  # noqa: E731
                                               dt))
    _, first, held = dweights.share(cfg)
    n_rows = int(cell["output"]["max"])
    lows = ("fp8", "bf16", "bf16_forced") if control else ()
    gaps = {k: [] for k in ("f32",) + lows}
    routed, routed_fp8 = [], []
    for i, r in enumerate(picked):
        served = np.asarray(r["tokens"], np.int32)
        T, n = len(r["prompt"]), len(served)

        def run(tokens, prec, forced=None):
            ids = np.concatenate([r["prompt"], tokens[:-1]])
            ids = np.pad(ids, (0, _width(cell, len(ids) + n_rows)
                               - len(ids)))
            out, chosen = ref.logits_rows(
                top, layer, expert, cfg, (first, held), ids,
                jnp.int32(T - 1), n_rows, prec, forced)
            return np.asarray(out)[:n], np.asarray(chosen)

        out, chosen = run(served, "f32")
        gaps["f32"].append(compare.token_gaps(out, served))
        # the probe's tokens are the window's unless rounding fell
        # otherwise in another batch: then its own sequence is routed
        again = np.asarray(probe["tokens"][i], np.int32)
        routed.append(chosen if np.array_equal(again, served)
                      else run(again, "f32")[1])
        for low in lows:
            low_out, low_chosen = run(
                served, low.split("_")[0],
                chosen if low.endswith("forced") else None)
            gaps[low].append(compare.token_gaps(out, low_out.argmax(-1)))
            if low == "fp8":
                routed_fp8.append(low_chosen)
    spans, chunked = placed(probe, picked, cell["engine"]["prefill_chunk"])
    table = lambda chosen, span: sum(                          # noqa: E731
        _held_table(cfg, chosen[i], a, b) for i, a, b in span)
    steps = list(zip(probe["steps"], spans, chunked))
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros(0)  # noqa: E731
    out = {"program": {**gap_numbers(cat(gaps["f32"])), **route_numbers(
        [(s["per_expert"], table(routed, span), c)
         for s, span, c in steps if span])}}
    if control:
        out["control_fp8"] = {
            **gap_numbers(cat(gaps["fp8"])), **route_numbers(
                [(table(routed_fp8, span), table(routed, span), c)
                 for s, span, c in steps if span])}
        for low in lows[1:]:
            out["reference_" + low] = gap_numbers(cat(gaps[low]))
    return out


#: the numbers that decide ``correct``; the others are shown beside them
COMPARED = ("token_gap_mean", "token_gap_p99", "route_disagreement",
            "route_disagreement_decode")


def gap_numbers(gaps):
    """The numbers of a run's token gaps."""
    if not len(gaps):
        return {"token_gap_mean": None, "token_gap_p99": None}
    return {"token_gap_mean": float(gaps.mean()),
            "token_gap_p99": float(compare.percentile(list(gaps), 99)),
            "token_gap_p95": float(compare.percentile(list(gaps), 95)),
            "token_gap_not_compared": float(gaps.max()),
            "tokens_not_the_references_first": int((gaps > 0).sum()),
            "checked_tokens": len(gaps)}


def check(obs, cell, cfg, seed):
    picked = obs["picked"]
    if not picked:
        return {k: float("inf") for k in COMPARED}, {"checked_requests": 0}
    got = reference_side(picked, obs["route_probe"], cell, cfg,
                         seed)["program"]
    numbers = {k: float("inf") if got[k] is None else got[k]
               for k in COMPARED}
    return numbers, {
        "checked_requests": len(picked),
        "longest_checked": max(len(r["prompt"]) for r in picked),
        "probe_steps": len(obs["route_probe"]["steps"]),
        "route_agreement": 1.0 - numbers["route_disagreement"],
        **{k: v for k, v in got.items() if k not in COMPARED}}


def serve(engine, cell, cfg, seed, seconds, trace, keep_trace=None):
    """Warm-up, the window, the expert layers' counts over it, and the
    routing probe: ``obs``."""
    warm_up(engine, cell, cfg, seed)
    before = _counts(engine)
    obs = window(engine, cell, cfg, seed, seconds, trace, keep_trace)
    obs["moe"] = _moe_since(engine, before)
    obs["memory_peak_bytes"] = harness.memory_peak_bytes()
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
            "finished": sum(_finished(r) for r in obs["requests"]),
            "offered": len(obs["requests"])}


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
