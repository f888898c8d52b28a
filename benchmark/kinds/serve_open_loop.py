"""Kind ``serve_open_loop``: independent users against one ``LLMEngine``.

The timed path is ``LLMEngine.add_request`` / ``step`` on the window's own
requests.  One thread: submit every request that is due, call ``step()``,
stamp what it returned; sleep only when nothing is live and nothing is due.
Requests are due on a schedule fixed by the cell's file and the seed, and
every latency is counted from the instant a request was DUE, so a stall
shows in the requests behind it.  After the window the run drains for at
most ``drain_limit_s``; what is unfinished then has failed.

A cell's file gives ``engine`` (the engine's keyword arguments),
``prompt``, ``output``, ``rate_per_s``, ``arrival_cv``,
``shared_prefix_tokens``, ``drain_limit_s``, ``check_min_tokens``,
``check_max_requests``, ``trace_seconds`` and ``limits``.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, harness, traffic
from benchmark.reference import gpt as ref_gpt


def build(cell, cfg, seed):
    from paddle_tpu.serving import LLMEngine
    model = harness.build_model(cfg, seed)
    model.eval()
    return LLMEngine(model, **cell["engine"])


def prefill_buckets(engine):
    b, out = engine.min_bucket, []
    while b < engine.prefill_chunk:
        out.append(b)
        b *= 2
    return out + [engine.prefill_chunk]


def warm_up(engine, cell, cfg, seed):
    """Compile the decode program and every prefill bucket, on prompts of
    other tokens than the window's."""
    prompts = traffic.warmup_prompts(cell, seed, cfg["vocab_size"],
                                     prefill_buckets(engine))
    handles = [engine.add_request(p, max_new_tokens=2, seed=0)
               for p in prompts]
    for _ in range(64 * len(handles)):
        if all(h.is_finished for h in handles):
            return
        engine.step()
    raise RuntimeError("warm-up requests did not finish")


class _Loop:
    """The serving loop and everything it stamps."""

    def __init__(self, engine, reqs, with_stats):
        from paddle_tpu.serving.engine import EngineBackpressure
        self.refusal = EngineBackpressure
        self.engine, self.reqs, self.with_stats = engine, reqs, with_stats
        self.next = 0
        self.by_handle = {}
        # per engine step: start, end, prefill (tokens, sum of live
        # lengths), live lengths of the rows decoded, blocks in use
        self.steps = []
        self.t0 = time.perf_counter()
        for r in reqs:
            r.update(submit_s=None, admitted_s=None, token_s=[],
                     handle=None, refused=None, first_step=None)

    def now(self):
        return time.perf_counter() - self.t0

    def _submit_due(self, now):
        while self.next < len(self.reqs) and \
                self.reqs[self.next]["due_s"] <= now:
            r = self.reqs[self.next]
            self.next += 1
            with harness.span("bench.add_request"):
                try:
                    r["handle"] = self.engine.add_request(
                        r["prompt"], max_new_tokens=r["max_new_tokens"],
                        seed=0, block=False)
                    self.by_handle[id(r["handle"])] = r
                except self.refusal as e:
                    r["refused"] = repr(e)
            r["submit_s"] = self.now()

    def _stamp(self, events, t, rec):
        for ev in events:
            r = self.by_handle.get(id(ev["request"]))
            if r is None:
                continue
            if ev["type"] == "admitted":
                r["admitted_s"] = t
            elif ev["type"] == "token":
                r["token_s"].append(t)
                if ev["index"] == 0:
                    r["first_step"] = len(self.steps)
                else:
                    rec["decode_live"].append(len(r["prompt"])
                                              + ev["index"])

    def run(self, until_s, drain=False):
        """Pump until ``until_s`` on the loop's clock; with ``drain`` stop
        earlier once nothing is live and nothing is left to submit."""
        eng = self.engine
        while True:
            now = self.now()
            if now >= until_s:
                return
            self._submit_due(now)
            if eng.has_work():
                rec = {"start": now, "decode_live": [], "prefill": [0, 0]}
                with harness.span("bench.engine_step"):
                    events = eng.step()
                rec["end"] = self.now()
                with harness.span("bench.read_tokens"):
                    self._stamp(events, rec["end"], rec)
                    if self.with_stats:
                        st = eng.stats()
                        rec["blocks_used"] = st["blocks_used"]
                        rec["blocks_total"] = st["blocks_total"]
                self.steps.append(rec)
            elif self.next >= len(self.reqs):
                if drain:
                    return
                with harness.span("bench.idle"):
                    time.sleep(min(until_s - now, 0.001))
            else:
                with harness.span("bench.idle"):
                    time.sleep(max(0.0, min(
                        self.reqs[self.next]["due_s"] - now, until_s - now,
                        0.001)))

    def close(self):
        """Keep what the requests produced and let go of their handles,
        each of which holds the engine (and so its weights and pool)."""
        for r in self.reqs:
            h = r.pop("handle")
            r["tokens"] = [] if h is None else [int(t) for t in h.tokens]
            r["finished"] = (
                h is not None and h.is_finished
                and h.finish_reason == "length"
                and len(r["tokens"]) == r["max_new_tokens"]
                and len(r["token_s"]) == r["max_new_tokens"])
        self.by_handle.clear()
        self.engine = None

    def place_prefill(self, chunk):
        """Put each request's prefill chunks on the steps that ran them:
        the last on the step that gave its first token, the others on the
        steps before it, one a step."""
        for r in self.reqs:
            if r["first_step"] is None:
                continue
            T = len(r["prompt"])
            starts = list(range(0, T, chunk))
            for j, start in enumerate(starts):
                s = r["first_step"] - (len(starts) - 1 - j)
                if 0 <= s < len(self.steps):
                    take = min(chunk, T - start)
                    pf = self.steps[s]["prefill"]
                    pf[0] += take
                    pf[1] += take * start + take * (take + 1) // 2


def window(engine, cell, cfg, seed, seconds, trace, keep_trace=None):
    from paddle_tpu.profiler import counters
    reqs = traffic.serve_requests(cell, seed, seconds, cfg["vocab_size"])
    before = counters.snapshot()
    loop = _Loop(engine, reqs, with_stats=bool(trace))
    obs, tracer = {}, None
    if trace:
        # The last ``trace_seconds`` of the window run under the profiler.
        # Starting and stopping it stalls this thread, so what is read from
        # the host's stamps is read from the part before it
        # (``obs["untraced_s"]``), and the trace is reduced after the drain.
        obs["untraced_s"] = max(seconds - cell["trace_seconds"], 0.0)
        loop.run(obs["untraced_s"])
        tracer = harness.Tracer(keep_trace)
        with tracer.window():
            loop.run(seconds)
        obs["traced"] = (tracer.t0 - loop.t0, tracer.t1 - loop.t0)
    loop.run(seconds)
    obs["retraces"] = counters.delta(before).get("serving.retraces", 0)
    loop.run(seconds + cell["drain_limit_s"], drain=True)
    loop.place_prefill(engine.prefill_chunk)
    loop.close()
    if tracer is not None:
        obs["trace"] = tracer.reduce()
    obs.update(steps=loop.steps, requests=reqs, seconds=seconds,
               t_start=loop.t0, drained_s=loop.now())
    return obs


def _finished(r):
    return r["finished"]


def end_to_end(obs):
    """The cell's end-to-end metrics from the stamps of all offered
    requests; a refused, failed or unfinished request sorts last."""
    reqs, seconds = obs["requests"], obs["seconds"]
    never = (obs["drained_s"] + 1.0) * 1e3
    ttft = [(r["token_s"][0] - r["due_s"]) * 1e3 if _finished(r) else never
            for r in reqs]
    itl = [(b - a) * 1e3 for r in reqs for a, b in
           zip(r["token_s"], r["token_s"][1:])]
    in_window = sum(t <= seconds for r in reqs for t in r["token_s"])
    return {"ttft_p90_ms": compare.percentile(ttft, 90),
            "itl_p90_ms": compare.percentile(itl, 90) if itl else never,
            "serve_tokens_per_s": in_window / seconds}


# ---------------------------------------------------------------------------
# the reference's side
# ---------------------------------------------------------------------------
def sample(obs, cell, seed):
    """The finished requests the reference is run over: the longest, and
    others drawn from the seed until ``check_min_tokens`` served tokens or
    ``check_max_requests`` requests."""
    done = [r for r in obs["requests"] if _finished(r)]
    if not done:
        return []
    done.sort(key=lambda r: -(len(r["prompt"]) + r["max_new_tokens"]))
    rest = done[1:]
    order = np.random.default_rng([int(seed), 2]).permutation(len(rest))
    picked = [done[0]]
    for i in order:
        if (sum(r["max_new_tokens"] for r in picked)
                >= cell["check_min_tokens"]
                or len(picked) >= cell["check_max_requests"]):
            break
        picked.append(rest[i])
    return picked


def reference_gaps(picked, cell, cfg, seed, control=False):
    """For every served token of the sampled requests, how far its
    reference logit lies below the reference's best.  With ``control`` also
    the same gap for the token that the 8-bit-float reference puts first at each
    position (read over the same prompts and served tokens)."""
    params = harness.reference_params(cfg, seed)
    n_rows = int(cell["output"]["max"])
    args = (n_rows, cfg["n_heads"], cfg["layer_norm_epsilon"])
    gaps, control_gaps = [], []
    for r in picked:
        served = np.asarray(r["tokens"], np.int32)
        T, n = len(r["prompt"]), len(served)
        ids = np.concatenate([r["prompt"], served[:-1]])
        width = -(-(T - 1 + n_rows) // n_rows) * n_rows
        ids = np.pad(ids, (0, width - len(ids)))
        first = jnp.int32(T - 1)
        ref = np.asarray(ref_gpt.logits_rows(params, jnp.asarray(ids), first, *args,
                               "f32"))[:n]
        gaps.append(compare.token_gaps(ref, served))
        if control:
            low = np.asarray(ref_gpt.logits_rows(params, jnp.asarray(ids), first, *args,
                                   "fp8"))[:n]
            control_gaps.append(compare.token_gaps(ref, low.argmax(-1)))
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros(0)
    return cat(gaps), cat(control_gaps)


def check(obs, cell, cfg, seed):
    picked = sample(obs, cell, seed)
    gaps, _ = reference_gaps(picked, cell, cfg, seed)
    worst = float(gaps.max()) if len(gaps) else float("inf")
    return ({"token_gap": worst},
            {"checked_requests": len(picked), "checked_tokens": len(gaps)})


def dispose(engine):
    """Let go of the pool and the weights (the caller drops its own name
    for the engine too).  The model object itself outlives its engine: the
    package's per-model program cache is a ``WeakKeyDictionary`` whose
    values (the jitted closures) hold their own key.  So its parameters'
    arrays are dropped one by one (PERF.md, Open questions)."""
    engine.release_kv()
    for _, p in engine.model.named_parameters():
        p._data = None
    engine._w = None
    harness.free_device()


# ---------------------------------------------------------------------------
def calibrate(cell, cfg, seed, seconds, control):
    """The readings a limit is set from, for one seed: a short window at
    the cell's own load, then the served tokens' gaps under the reference
    and, with ``control``, the gaps of the tokens that the 8-bit-float reference
    puts first at the same positions."""
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
    """The sweep that finds the knee: one engine, each rate offered for a
    window of ``seconds`` and then drained.  Yields, per rate, how many
    requests were due and not yet finished at each quarter of the window
    (a backlog that grows from quarter to quarter is past the knee)."""
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
        half = [[(r["token_s"][0] - r["due_s"]) * 1e3 for r in reqs
                 if _finished(r) and lo <= r["due_s"] < hi]
                for lo, hi in ((0, seconds / 2), (seconds / 2, seconds))]
        yield {"rate_per_s": rate, "offered": len(reqs),
               "finished": sum(map(_finished, reqs)),
               "backlog_at_quarters": backlog,
               "ttft_p50_ms_by_half": [compare.percentile(h, 50) if h
                                       else None for h in half],
               "drained_s": obs["drained_s"], **e2e,
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
