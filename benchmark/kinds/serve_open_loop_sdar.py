"""Kind ``serve_open_loop_sdar``: ``serve_open_loop``'s loop over an
``sdar_moe`` model, which generates by diffusion over blocks.

The timed path, the schedule, the end-to-end metrics, the sampling of
requests for the check, the warm-up and the disposal are
``serve_open_loop``'s own, imported and not copied.  What is this
family's: ``build`` (the model of ``paddle_tpu/models/sdar.py`` over the
weights of ``sdar_weights.py``, handed to its constructor one tensor at a
time, so nothing is drawn twice), what the loop stamps (a token's
``reveal_step``, and the engine's ``block`` event a commit: the whole
block, its reveal passes, how many passes it took), its own placing of
work on steps, and ``check``.

**Placing.**  A decode launch is one pass of every running row's block
and most passes emit nothing, so the steps a request worked on are read
from its commits: a block committed on step ``s`` after ``P`` passes had
them on steps ``s - P + 1 .. s`` (a running row has one pass a step), and
the prompt's chunks lie one a step on the steps up to the first pass of
the first block (``serve_open_loop.place_prefill`` would put the last
chunk on the step of the first token, which here comes a block later).
A step's record gains ``passes``: the first position of every block it
passed.

**check.**  Every served token is compared AT THE PASS THAT REVEALED IT.
One padded reference pass a request holds all of its passes: the final
tokens once, under the block-causal mask, then each denoising pass's
state of each block (tokens revealed before that pass, mask tokens
elsewhere) as four more rows at the block's positions that see the final
tokens of the earlier blocks and their own state only (the training mask
of the paper, arXiv:2510.06303).  From the reference's logits at those
rows: ``token_gap_mean`` / ``token_gap_p99``, how far the served token's
logit lies below the reference's best there; ``reveal_gap_mean``, how
far the revealed position's log-confidence (the reference's own best
token's log-probability) lies below that of the reference's most confident
masked position: 0 where the program reveals what the reference would.
The widest of each and the reveal gaps' 99th percentile are printed and
not compared (one router flip in a hundred moves a whole expert's output
in a token's residual stream: ``serve_open_loop_deepseek_v2``'s finding,
and the same experts' code; the percentile's 8-bit control reads only 1.7
times the program's largest).  ``route_disagreement`` is that kind's
number, imported: the sampled requests are served again through the same
engine, the expert layers' counts are read after every step, and the
reference gives the same table from its own routers over the rows that step passed (a chunk's
tokens, a denoising pass's state rows, a commit pass's final rows), so
that a program that leaves experts out, or routes rows that are not
running, cannot pass.

A cell's file takes the keys ``serve_open_loop`` takes, and
``denoise_steps`` / ``reveal_threshold``, which have to be the
configuration's own defaults (the loop submits requests without knobs).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, harness, traffic
from benchmark import sdar_weights as sweights
from benchmark.kinds.serve_open_loop import (_Loop, _finished, dispose,
                                             end_to_end, sample, warm_up)
from benchmark.kinds.serve_open_loop_deepseek_v2 import (_counts, _moe_since,
                                                         disagreement)
from benchmark.reference import sdar as ref

#: the reference pads a request's rows to one of these widths (doubling),
#: so that it compiles a handful of times whatever the seed picks
_MIN_WIDTH = 2048
#: rows projected onto the vocabulary at a time
_ROWS = 512


def build(cell, cfg, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models.sdar import (SdarConfig, SdarMoeForCausalLM,
                                        param_shapes)
    from paddle_tpu.serving import LLMEngine
    if (cell.get("denoise_steps", cfg["denoising_steps"])
            != cfg["denoising_steps"]
            or cell.get("reveal_threshold") is not None):
        raise ValueError("the cell's denoise_steps / reveal_threshold are "
                         "not the configuration's defaults, and the loop "
                         "submits requests without knobs")
    paddle.seed(int(seed) & 0x7FFFFFFF)
    config = SdarConfig.from_hf(
        cfg, block_length=cfg["block_length"],
        denoising_steps=cfg["denoising_steps"],
        mask_token_id=cfg["mask_token_id"],
        initializer_range=cfg["initializer_range"],
        dtype=cfg["compute_dtype"])
    if set(param_shapes(config)) != set(sweights.PROGRAM_TENSORS):
        raise ValueError(
            "the model's parameters are not the benchmark's: "
            f"{sorted(set(param_shapes(config)) ^ set(sweights.PROGRAM_TENSORS))}")
    # the constructor draws nothing: each tensor is the benchmark's, made
    # from --seed when the constructor asks for it (shapes and types are
    # held to the model's there)
    model = SdarMoeForCausalLM(config, tensors=lambda name: (
        sweights.program_tensor(cfg, seed, name, cfg["compute_dtype"])))
    model.eval()
    return LLMEngine(model, **cell["engine"])


# ---------------------------------------------------------------------------
# the loop's stamps and the placing of work on steps
# ---------------------------------------------------------------------------
class _BlockLoop(_Loop):
    def __init__(self, engine, reqs, with_stats):
        super().__init__(engine, reqs, with_stats)
        self.block = engine.block_length
        for r in reqs:
            r.update(blocks=[], reveal_steps=[])

    def _stamp(self, events, t, rec):
        B = self.block
        for ev in events:
            r = self.by_handle.get(id(ev["request"]))
            if r is None:
                continue
            if ev["type"] == "admitted":
                r["admitted_s"] = t
            elif ev["type"] == "token":
                r["token_s"].append(t)
                r["reveal_steps"].append(ev["reveal_step"])
                if ev["index"] == 0:
                    r["first_step"] = len(self.steps)
                # the keys the token's position attends to: everything up
                # to the end of its block
                at = len(r["prompt"]) + ev["index"]
                rec["decode_live"].append(at // B * B + B)
            elif ev["type"] == "block":
                r["blocks"].append({
                    "step": len(self.steps), "start": ev["start"],
                    "passes": ev["passes"], "tokens": ev["tokens"],
                    "reveal_steps": ev["reveal_steps"]})

    def place_prefill(self, chunk):
        place(self.reqs, self.steps, chunk, self.block)


def chunk_spans(T, chunk, B):
    """``[(from, to)]``: the chunks of a prompt of ``T`` tokens, whole
    blocks only (the remainder opens the first generated block)."""
    whole = T // B * B
    return [(a, min(a + chunk, whole)) for a in range(0, whole, chunk)]


def place(reqs, steps, chunk, B):
    """Put each request's passes and chunks on the steps that ran them
    (see the module's docstring): ``steps[s]["passes"]`` gains the first
    position of every block passed, ``steps[s]["prefill"]`` the chunk's
    tokens and the keys they attend to between them."""
    for s in steps:
        s.setdefault("passes", [])
    for r in reqs:
        for b in r["blocks"]:
            for k in range(b["passes"]):
                if 0 <= b["step"] - k < len(steps):
                    steps[b["step"] - k]["passes"].append(b["start"])
        if not r["blocks"]:
            continue
        first = r["blocks"][0]
        spans = chunk_spans(len(r["prompt"]), chunk, B)
        for j, (a, b) in enumerate(spans):
            s = first["step"] - first["passes"] + 1 - (len(spans) - 1 - j)
            if 0 <= s < len(steps):
                nb = (b - a) // B
                pf = steps[s]["prefill"]
                pf[0] += b - a
                pf[1] += (b - a) * a + B * B * nb * (nb + 1) // 2


def window(engine, cell, cfg, seed, seconds, trace, keep_trace=None):
    """``serve_open_loop.window`` with this family's loop, and the
    window's own counter delta handed on as ``obs["counters"]``."""
    from paddle_tpu.profiler import counters
    reqs = traffic.serve_requests(cell, seed, seconds, cfg["vocab_size"])
    before = counters.snapshot()
    loop = _BlockLoop(engine, reqs, with_stats=bool(trace))
    obs, tracer = {}, None
    if trace:
        obs["untraced_s"] = max(seconds - cell["trace_seconds"], 0.0)
        loop.run(obs["untraced_s"])
        tracer = harness.Tracer(keep_trace)
        with tracer.window():
            loop.run(seconds)
        obs["traced"] = (tracer.t0 - loop.t0, tracer.t1 - loop.t0)
    loop.run(seconds)
    obs["counters"] = counters.delta(before)
    obs["retraces"] = obs["counters"].get("serving.retraces", 0)
    loop.run(seconds + cell["drain_limit_s"], drain=True)
    loop.place_prefill(engine.prefill_chunk)
    loop.close()
    if tracer is not None:
        obs["trace"] = tracer.reduce()
    obs.update(steps=loop.steps, requests=reqs, seconds=seconds,
               t_start=loop.t0, drained_s=loop.now())
    return obs


# ---------------------------------------------------------------------------
# the routing probe
# ---------------------------------------------------------------------------
def route_probe(engine, picked):
    """Serve the sampled requests again, each submitted when the one
    before it has committed its first block, and read the expert layers'
    counts after every step: ``{"blocks": [a request's block events],
    "steps": [{"per_expert": [layers, experts] the step added, "routed":
    the rows it routed}]}``."""
    if not picked:
        return None
    give_up = time.perf_counter() + 120.0
    while engine.has_work() and time.perf_counter() < give_up:
        engine.step()
    handles, steps = [], []
    blocks = [[] for _ in picked]
    last = _counts(engine)
    give_up = time.perf_counter() + 300.0
    while time.perf_counter() < give_up:
        if len(handles) < len(picked) and (not handles
                                           or blocks[len(handles) - 1]):
            r = picked[len(handles)]
            handles.append(engine.add_request(
                r["prompt"], max_new_tokens=r["max_new_tokens"], seed=0))
        elif not engine.has_work():
            break
        events = engine.step()
        now = _counts(engine)
        place_of = {id(h): i for i, h in enumerate(handles)}
        for ev in events:
            if ev["type"] == "block" and id(ev["request"]) in place_of:
                blocks[place_of[id(ev["request"])]].append({
                    "step": len(steps), "start": ev["start"],
                    "passes": ev["passes"], "tokens": ev["tokens"],
                    "reveal_steps": ev["reveal_steps"]})
        steps.append({"per_expert": now["per_expert"] - last["per_expert"],
                      "routed": now["tokens"] - last["tokens"]})
        last = now
    if len(handles) < len(picked) or not all(h.is_finished for h in handles):
        raise RuntimeError("the routing probe did not serve its requests")
    return {"blocks": blocks, "steps": steps}


def placed(probe, picked, chunk, B):
    """What each step of the probe routed: ``[[(request, "main", from,
    to) or (request, "state", block, pass)]]`` and whether the step
    carried a chunk.  ``main`` rows are positions of the final sequence
    (a chunk's tokens, a commit pass's block), ``state`` rows a denoising
    pass's."""
    n = len(probe["steps"])
    spans = [[] for _ in range(n)]
    chunked = [False] * n
    for i, blocks in enumerate(probe["blocks"]):
        for bi, b in enumerate(blocks):
            spans[b["step"]].append((i, "main", b["start"], b["start"] + B))
            for k in range(b["passes"] - 1):
                spans[b["step"] - (b["passes"] - 1 - k)].append(
                    (i, "state", bi, k))
        first = blocks[0]
        cs = chunk_spans(len(picked[i]["prompt"]), chunk, B)
        for j, (a, b) in enumerate(cs):
            at = first["step"] - first["passes"] + 1 - (len(cs) - 1 - j)
            if at < 0:
                raise RuntimeError("the routing probe passed a block "
                                   "before its chunks had steps")
            spans[at].append((i, "main", a, b))
            chunked[at] = True
    for step, span in zip(probe["steps"], spans):
        rows = sum(s[3] - s[2] if s[1] == "main" else B for s in span)
        if step["routed"] != rows:
            raise RuntimeError(
                "the routing probe cannot say which rows a step routed: "
                f"{step['routed']} by the program's count, {span} by its "
                "events")
    return spans, chunked


# ---------------------------------------------------------------------------
# the reference's side
# ---------------------------------------------------------------------------
def layout(prompt, blocks, B, mask_id):
    """One padded reference pass for a request: ``(ids, pos, mask,
    state_row)``.  The final tokens ``0 .. M - 1`` under the block-causal
    mask, then for each block and each of its denoising passes ``B`` rows
    at the block's positions holding the block as that pass saw it, which
    see the final tokens of the earlier blocks and themselves;
    ``state_row[block, pass]`` is the first of them.  Rows of padding see
    themselves."""
    T = len(prompt)
    whole = T // B * B
    final = list(prompt[:whole]) + [t for b in blocks for t in b["tokens"]]
    M = len(final)
    ids, pos, state_row = list(final), list(range(M)), {}
    for bi, b in enumerate(blocks):
        for k in range(b["passes"] - 1):
            state_row[bi, k] = len(ids)
            ids += [t if r < k else mask_id
                    for t, r in zip(b["tokens"], b["reveal_steps"])]
            pos += range(b["start"], b["start"] + B)
    W = _MIN_WIDTH
    while W < len(ids):
        W *= 2
    mask = np.eye(W, dtype=bool)
    mask[:M, :M] = ref.block_causal(M, B)
    for (bi, k), row in state_row.items():
        mask[row:row + B, :blocks[bi]["start"]] = True
        mask[row:row + B, row:row + B] = True
    pad = W - len(ids)
    return (np.asarray(ids + [mask_id] * pad, np.int32),
            np.asarray(pos + [0] * pad, np.int32), mask, state_row)


@jax.jit
def _row_stats(lg, tok, low=None):
    """Of logits ``lg [n, V]``: each row's best, the log of its sum of
    exponentials, the logit of ``tok [n]``; with the logits ``low`` of a
    run in a lower precision also ``lg`` at that run's best token and that
    run's own log-confidence."""
    best = lg.max(-1)
    out = [best, jax.nn.logsumexp(lg, axis=-1),
           jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]]
    if low is not None:
        out += [jnp.take_along_axis(lg, low.argmax(-1)[:, None], -1)[:, 0],
                low.max(-1) - jax.nn.logsumexp(low, axis=-1)]
    return jnp.stack(out)


def _params(cfg, seed):
    dt = cfg["compute_dtype"]
    wide = lambda tree: {n: x.astype(jnp.float32)              # noqa: E731
                         for n, x in tree.items()}
    return {"config": cfg, "top": wide(sweights.top(cfg, seed, dt)),
            "layer": lambda l: wide(sweights.layer(cfg, seed, l, dt)),
            "expert": lambda l, e: wide(sweights.expert(cfg, seed, l, e,
                                                        dt))}


def reference_request(params, r, blocks, cfg, lows=()):
    """The reference over one request's passes: ``(state_row, stats,
    chosen)``.  ``stats[row]`` for every state row, ``chosen {prec:
    [layers, W, k]}`` the routers' choices; ``lows`` names lower
    precisions to run beside float32 (``stats`` then holds the first of
    them too)."""
    B = cfg["block_length"]
    ids, pos, mask, state_row = layout(r["prompt"], blocks, B,
                                       cfg["mask_token_id"])
    h, chosen = {}, {}
    for prec in ("f32",) + tuple(lows):
        h[prec], c = ref.hidden(params, ids, mask, pos, prec)
        chosen[prec] = np.asarray(c)
    rows = np.asarray(sorted(x for row in state_row.values()
                             for x in range(row, row + B)), np.int32)
    # the token each state row is judged by: the block's final token there
    tok = np.zeros(len(ids), np.int32)
    for (bi, k), row in state_row.items():
        tok[row:row + B] = blocks[bi]["tokens"]
    stats = {}
    for a in range(0, len(rows), _ROWS):
        part = np.pad(rows[a:a + _ROWS], (0, -len(rows[a:a + _ROWS]) % _ROWS))
        lg = ref.project(params, h["f32"], part, "f32")
        low = (ref.project(params, h[lows[0]], part, lows[0]) if lows
               else None)
        got = np.asarray(_row_stats(lg, jnp.asarray(tok[part]), low),
                         np.float64)
        for j, row in enumerate(rows[a:a + _ROWS]):
            stats[int(row)] = got[:, j]
    return state_row, stats, chosen


def gaps_of(r, blocks, state_row, stats, B, control=False):
    """``(token gaps, reveal gaps)`` of one request: for every served
    token the gap at the pass that revealed it; for every pass the gap of
    each position it revealed.  With ``control`` the same for the token
    and the position that the lower-precision run (``stats`` rows 3 and
    4) puts first."""
    T, n = len(r["prompt"]), r["max_new_tokens"]
    token, reveal = [], []
    for bi, b in enumerate(blocks):
        for k in range(b["passes"] - 1):
            row = state_row[bi, k]
            st = np.stack([stats[row + p] for p in range(B)])
            conf = st[:, 0] - st[:, 1]           # log-confidence, float32
            masked = [p for p in range(B) if b["reveal_steps"][p] >= k]
            best = max(conf[p] for p in masked)
            if control:
                low_first = max(masked, key=lambda p: (st[p, 4], -p))
                reveal.append(best - conf[low_first])
            for p in masked:
                if b["reveal_steps"][p] != k:
                    continue
                if not control:
                    reveal.append(best - conf[p])
                if b["start"] + p < T + n:       # served, not cut off
                    token.append(st[p, 0] - st[p, 3 if control else 2])
    return token, reveal


def reference_side(picked, probe, cell, cfg, seed, control=False):
    """``{"program": numbers}``, and with ``control`` ``"control_fp8"``:
    the 8-bit-float reference against the float32 one in the same numbers
    (its first token and its first position to reveal at every pass, its
    routers over the same rows)."""
    B = cfg["block_length"]
    params = _params(cfg, seed)
    lows = ("fp8",) if control else ()
    gaps = {k: ([], []) for k in ("program",) + (("control_fp8",)
                                                 if control else ())}
    chosen = []
    for i, r in enumerate(picked):
        state_row, stats, ch = reference_request(params, r, r["blocks"],
                                                 cfg, lows)
        for name in gaps:
            t, v = gaps_of(r, r["blocks"], state_row, stats, B,
                           name != "program")
            gaps[name][0].extend(t)
            gaps[name][1].extend(v)
        again = probe["blocks"][i]
        if [(b["tokens"], b["reveal_steps"]) for b in again] != [
                (b["tokens"], b["reveal_steps"]) for b in r["blocks"]]:
            # rounding fell otherwise in the probe's batches: its own
            # sequence is routed
            state_row, _, ch = reference_request(params, r, again, cfg, lows)
        chosen.append((state_row, ch))
    spans, chunked = placed(probe, picked, cell["engine"]["prefill_chunk"],
                            B)

    def table(prec, span):
        total = 0
        for s in span:
            state_row, ch = chosen[s[0]]
            a, b = ((s[2], s[3]) if s[1] == "main" else
                    (state_row[s[2], s[3]], state_row[s[2], s[3]] + B))
            total = total + (ch[prec][:, a:b, :, None]
                             == np.arange(cfg["num_experts"])).sum((1, 2))
        return total

    steps = [(s, span, c) for s, span, c in
             zip(probe["steps"], spans, chunked) if span]
    out = {"program": {**gap_numbers(*gaps["program"]), **route_numbers(
        [(s["per_expert"], table("f32", span), c) for s, span, c in steps])}}
    if control:
        out["control_fp8"] = {
            **gap_numbers(*gaps["control_fp8"]), **route_numbers(
                [(table("fp8", span), table("f32", span), c)
                 for s, span, c in steps])}
    return out


def route_numbers(tables):
    return {"route_disagreement": disagreement(
                [(a, b) for a, b, _ in tables]),
            "route_disagreement_decode_not_compared": disagreement(
                [(a, b) for a, b, chunked in tables if not chunked])}


#: the numbers that decide ``correct``; the others are shown beside them
COMPARED = ("token_gap_mean", "token_gap_p99", "reveal_gap_mean",
            "route_disagreement")


def gap_numbers(token, reveal):
    if not len(token) or not len(reveal):
        return {k: None for k in COMPARED[:3]}
    token, reveal = np.asarray(token), np.asarray(reveal)
    return {"token_gap_mean": float(token.mean()),
            "token_gap_p99": float(compare.percentile(list(token), 99)),
            "reveal_gap_mean": float(reveal.mean()),
            "reveal_gap_p99_not_compared": float(
                compare.percentile(list(reveal), 99)),
            "token_gap_not_compared": float(token.max()),
            "reveal_gap_not_compared": float(reveal.max()),
            "tokens_not_the_references_first": int((token > 0).sum()),
            "reveals_not_the_references_first": int((reveal > 0).sum()),
            "checked_tokens": len(token), "checked_reveals": len(reveal)}


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
            "offered": len(obs["requests"]), **end_to_end(obs)}


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
        e2e = end_to_end(obs)
        e2e.pop("itl_p90_ms")
        yield {"rate_per_s": rate, "offered": len(reqs),
               "finished": sum(map(_finished, reqs)),
               "backlog_at_quarters": backlog,
               "drained_s": obs["drained_s"], **e2e,
               "step_p50_ms": 1e3 * float(np.median(
                   [s["end"] - s["start"] for s in obs["steps"]])),
               "rows_a_launch_mean": float(np.mean(
                   [len(s["passes"]) for s in obs["steps"]
                    if s["passes"]] or [0])),
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
    e2e = end_to_end(obs)
    e2e.pop("itl_p90_ms")       # three gaps in four are 0 by construction
    return {
        "attempted": len(reqs),
        "failed": sum(not _finished(r) for r in reqs),
        "end_to_end": e2e,
        "numbers": numbers, "where": where, "obs": obs,
        "memory_peak_bytes": obs["memory_peak_bytes"],
        "t_window_start": obs["t_start"],
    }
