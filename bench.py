"""Benchmark: GPT causal-LM training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} for the
flagship leg, with per-leg detail under "legs".

Baseline anchor (BASELINE.md): the reference publishes no in-repo numbers;
the driver-defined north star is >=45% GPT MFU.  vs_baseline is true
model-FLOPs utilisation from 6*N FLOPs/token against the device's published
**bf16** peak (``_PEAKS``, keyed by ``device_kind``; an unknown kind is an
error).  With no TPU the bench exits non-zero and prints no number.

Legs (perf round 5):
- gpt760m (flagship MFU leg): "GPT-3 Large", batch 8 x 1024,
  recompute='selective_lean' (saves qkv+attn_out only; fc1 replays in bwd)
  — the largest model whose AdamW state (bf16 params + fp32 master + 2
  fp32 moments ~ 10.6G) fits the 15.75G chip.  Measured 0.468 MFU (512/512 flash blocks, r5 sweep).
- gpt125m (regression leg): round-4's config, batch 16 x 1024, selective
  remat — small-model overhead regression guard.  Runs twice: single-step
  dispatch, then fused multi-step dispatch (``fused_steps=K``, one XLA
  launch per K steps) — the reported ``fused_speedup`` is the
  dispatch-amortisation win on the leg most exposed to per-step python
  overhead.
- gpt125m_serve (serving leg): 64 staggered mixed-length requests through
  ``serving.LLMEngine`` (continuous batching over the paged K/V pool),
  with the first few verified token-identical against sequential
  ``GPT.generate`` — reports decode tokens/s for both, ``serve_speedup``,
  and TTFT / inter-token / queue-wait latency percentiles
  (p50/p95/p99 in ms) from the engine's mergeable histograms.
- gpt125m_paged (paged-KV leg): a mixed-length request set through an
  engine whose block pool holds ``max_slots`` sequences of ``S_max``,
  gating ≥2× that many peak admitted concurrent requests (a request
  reserves the blocks it can touch, not a row of ``S_max``); plus a
  64-request shared-system-prompt workload reporting TTFT p50/p95 and
  gating prefix-cache hits with strictly fewer prefill-chunk launches
  than a no-cache twin.
- gpt125m_tiered (KV-tiering leg): two-pass session traffic (every
  prompt queried twice) through paged engines whose block pools are cut
  to 1/2 and 1/4 of the working set with a pinned host-RAM KV tier
  covering the difference — cold radix leaves spill to host instead of
  being freed and page back on the second visit.  Gates token identity
  to sequential ``generate``, zero sheds under oversubscription, live
  spill/restore traffic, and decode tok/s at 2x oversubscription >=0.5x
  the ample-pool base; a 2-replica tiered fleet replay gates the
  router's host-aware prefix-affinity wins (``prefix_routed``) and the
  zero-lost invariant.
- gpt125m_spec (speculative-decoding leg): an aligned draft/target pair
  (shared embeddings, zeroed transformer blocks — acceptance ~1.0, so the
  leg measures the draft/verify machinery's ceiling) served greedily by
  ``LLMEngine(draft_model=...)`` vs the non-speculative
  baseline on the same prompts — reports acceptance rate, draft/verify
  dispatch counts, and net decode tok/s, gating token identity, zero
  steady retraces, ``accepted + rejected == drafted`` and ≥1.3× speedup.
- gpt125m_fleet (elastic-fleet leg): the same seeded request set through
  a 2-replica ``serving.ServingFleet`` clean, then with one replica
  killed mid-decode (``faultinject`` ``replica_crash``) — reports decode
  tokens/s for both and ``churn_retention``, and gates the durability
  invariants (zero lost requests, churn output token-identical to clean).
- gpt125m_mesh / gpt760m_mesh (multi-chip SPMD legs): the same fused
  training loop run mesh-native (``CompiledTrainStep(mesh=...)``, sharded
  donated carry, data-parallel batch staging) on the ``PTPU_MESH`` mesh
  (default ``dp2``; e.g. ``dp4`` or ``dp2mp2``), against a mesh(1) run of
  the identical code path as the per-chip baseline.  Reports total tok/s,
  tok/s/chip, weak-scaling efficiency ``(tok/s / n_chips) / tok/s(1)``
  and per-chip MFU; gates zero steady-state retraces/hydrates/binds and
  dispatches == steps/K on the mesh path, and ≥70% dp scaling efficiency
  on the chips.
- gpt760m_servemp (tensor-parallel serving leg, PTPU_BENCH=servemp with
  PTPU_MESH=mp2): the paged engine run mesh-native over the StateArena
  (``LLMEngine(mesh=...)`` — KV pool head-sharded, Megatron-sharded
  weights, replicated block-table/sampling operands, in-graph collectives
  only) against the unsharded engine at EQUAL admitted capacity.
  Reports decode tok/s/chip and per-chip KV-pool / weight HBM bytes;
  gates token identity, zero steady retraces, per-chip KV+weight bytes
  <= 0.6x the single-chip figure, and decode tok/s >= 0.9x unsharded
  (the 760m flagship).
- gpt125m_multitenant (multi-tenant LoRA serving leg): 6 adapter tenants
  through a 2-replica fleet whose per-replica AdapterArena holds only 4,
  so cold tenants page in and the LRU evicts idle ones.  A FAIR
  round-robin pass (tenants + base rows in one heterogeneous batch) and
  a NOISY pass (tenant 0 floods, plus an injected ``adapter_load_drop``)
  report decode tok/s, per-tenant-bucket TTFT/ITL tails, the flood
  bucket's ITL-p95 skew, and arena traffic (loads / evictions /
  arena_bytes / routed affinity wins); gates zero lost, token identity
  across repeats, recovery from the dropped load, and zero steady
  retraces — ONE compiled decode program serves every tenant mix.
Every training leg embeds a compact "metrics" block (loss / grad-norm /
tok/s / step-time / MFU stats from the zero-sync in-graph MetricsLogger
accumulators) plus a "goodput" block (the profiler.goodput wall-clock
ledger: compile/step bucket split and the accounted fraction); the serve
and fleet legs embed TTFT / inter-token / queue-wait percentiles, run
their measured pass under request tracing (sample=1 — the parity gates
prove it adds zero syncs/retraces) and embed a "trace" stage breakdown
saying WHERE the tail lives (queue vs prefill vs decode p50/p99/share);
the fleet leg additionally smoke-hits the live ops endpoint (OpsServer
/healthz + /traces over HTTP, ephemeral port) while the fleet is up; the
ckpt leg embeds save-latency percentiles; the mesh legs embed
per-compiled-program HBM bytes ("hbm") captured via XLA memory analysis
under FLAGS_device_telemetry.  The serve / paged / spec legs embed a
"devicetime" block (per-program device-time share / mean / MFU from the
FLAGS_device_time_sample ledger, captured in a short UNTIMED post-window
pass so the sampling fences never touch a gated number) —
``bench_compare.py --attribute`` diffs these shares to name the program
behind any regression.
Set PTPU_BENCH=125m|760m|serve|paged|paged_q|tiered|spec|ckpt|fleet|disagg|mesh|mesh760m|servemp|multitenant
to run a single leg.  PTPU_FUSED_STEPS sets the fused window length K (default 4; 1
disables the fused leg).  PTPU_MESH picks the mesh leg's axis degrees.
"""

import itertools
import json
import os
import time

import numpy as np


def _metrics_summary(logger, keys=("loss", "grad_norm", "tok_s",
                                  "step_time_s", "mfu")):
    """Compact per-metric stats from a ``MetricsLogger`` for the leg JSON."""
    if logger is None:
        return {}
    return {k: {f: round(float(x), 6) for f, x in s.items()}
            for k, s in logger.summary().items() if k in keys}


def _goodput_summary(ledger):
    """Compact wall-clock ledger block for the leg JSON (see
    profiler.goodput): where every second went, and how much of it was
    attributed to a named bucket (>=99% or the phase timings lie)."""
    r = ledger.report()
    return {"goodput": round(r["goodput"], 4),
            "accounted": round(r["accounted"], 4),
            "wall_s": round(r["wall_s"], 4),
            "buckets_s": {k: round(v, 4)
                          for k, v in r["buckets_s"].items() if v}}


def _sampled_devicetime(run_fn, sample=4, top=8):
    """Per-program device-time/MFU attribution block for one leg.

    Runs ``run_fn`` (a short UNTIMED window on the leg's already-warm
    engine) with ``FLAGS_device_time_sample=N`` + device telemetry on, so
    the ledger joins sampled fence times with AOT FLOPs/HBM stats, then
    restores the flags and returns ``devicetime.bench_block``.  Always
    runs AFTER the leg's gated timing windows: the sampled syncs (and the
    one-off AOT captures) never perturb a gated number."""
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.profiler import devicetime
    saved = {k: _flags.flag(k) for k in ("FLAGS_device_time_sample",
                                         "FLAGS_device_telemetry")}
    devicetime.reset()
    _flags.set_flags({"FLAGS_device_time_sample": int(sample),
                      "FLAGS_device_telemetry": True})
    try:
        run_fn()
        block = devicetime.bench_block(top=top)   # flags still live: the
        # block records the sample rate + joined MFU it measured with
    finally:
        _flags.set_flags(saved)
    devicetime.reset()
    return block


def _run_leg(cfg, batch, seq, iters, rounds, fused_steps=1):
    import paddle_tpu as paddle
    from paddle_tpu.io import Window
    from paddle_tpu.jit import CompiledTrainStep
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.profiler.goodput import GoodputLedger

    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
    labels = paddle.randint(0, cfg.vocab_size, [batch, seq])

    def loss_fn(m, x, l):
        return crit(m(x), l)

    k = max(1, int(fused_steps))
    # metrics=True: in-graph telemetry rides the donated carry — the MFU
    # this leg reports is also derivable from the harvested series
    step = CompiledTrainStep(model, loss_fn, opt, fused_steps=k,
                             metrics=True)
    if k > 1:
        win = Window(
            (paddle.to_tensor(np.stack([np.asarray(ids.numpy())] * k)),
             paddle.to_tensor(np.stack([np.asarray(labels.numpy())] * k))),
            k)
        dispatch = lambda: step(win)
    else:
        dispatch = lambda: step(ids, labels)
    # warmup / compile, timed per phase: 2 warmup dispatches in both modes.
    # Single-step mode traces 2 structures (empty accs then full); fused
    # mode runs window 1 as the priming single-step fallback (both acc
    # structures) and window 2 as the scan compile.  compile_s covers
    # hydrate + all traces + XLA compiles; first_step_s is the first fully
    # cached dispatch; steady_step_s is the measured median.
    ledger = GoodputLedger()
    ledger.start()
    with ledger.bucket("compile"):
        t0 = time.perf_counter()
        dispatch()
        dispatch().numpy()
        compile_s = time.perf_counter() - t0
    with ledger.bucket("step"):
        t0 = time.perf_counter()
        dispatch().numpy()
        first_step_s = time.perf_counter() - t0

    n_windows = max(1, iters // k)
    rates = []
    for _ in range(rounds):
        with ledger.bucket("step"):
            t0 = time.perf_counter()
            for _ in range(n_windows):
                loss = dispatch()
            loss.numpy()  # sync
            dt = time.perf_counter() - t0
        rates.append(batch * seq * k * n_windows / dt)
    ledger.stop()
    tokens_per_sec = float(np.median(rates))
    spread = (float(np.max(rates) - np.min(rates)) / tokens_per_sec
              if len(rates) > 1 else 0.0)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    phases = {"compile_s": round(compile_s, 4),
              "first_step_s": round(first_step_s, 4),
              "steady_step_s": round(batch * seq / tokens_per_sec, 6)}
    step.metrics_flush()  # harvest pending device refs at the leg boundary
    msum = _metrics_summary(step.metrics)
    gput = _goodput_summary(ledger)
    del step, model, opt  # free HBM before the next leg
    return tokens_per_sec, spread, n_params, phases, msum, gput


def _run_ckpt_leg(cfg, batch, seq, iters, fused_steps=1,
                  save_every_windows=2, seed=0):
    """Checkpointed-training overhead: the same steady dispatch loop run
    twice — bare, then with async ``resilience.CheckpointManager`` saves
    every ``save_every_windows`` windows (disk writes overlap the next
    window).  Reports the throughput overhead fraction and asserts the
    one-counter-gated-sync-per-save budget."""
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.io import Window
    from paddle_tpu.jit import CompiledTrainStep
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.profiler import counters
    from paddle_tpu.resilience import CheckpointManager

    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
    labels = paddle.randint(0, cfg.vocab_size, [batch, seq])

    def loss_fn(m, x, l):
        return crit(m(x), l)

    k = max(1, int(fused_steps))
    step = CompiledTrainStep(model, loss_fn, opt, fused_steps=k)
    if k > 1:
        win = Window(
            (paddle.to_tensor(np.stack([np.asarray(ids.numpy())] * k)),
             paddle.to_tensor(np.stack([np.asarray(labels.numpy())] * k))),
            k)
        dispatch = lambda: step(win)
    else:
        dispatch = lambda: step(ids, labels)
    dispatch()
    dispatch().numpy()  # warm: all traces + compiles done

    n_windows = max(save_every_windows, iters // k)
    t0 = time.perf_counter()
    for _ in range(n_windows):
        loss = dispatch()
    loss.numpy()
    base_s = time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as ckdir:
        mgr = CheckpointManager(ckdir, keep_last=2, async_save=True)
        before = counters.snapshot()
        t0 = time.perf_counter()
        gs = 0
        for i in range(n_windows):
            loss = dispatch()
            gs += k
            if (i + 1) % save_every_windows == 0:
                mgr.save(step, gs, blocking=False)
        loss.numpy()
        mgr.wait()
        ckpt_s = time.perf_counter() - t0
        delta = counters.delta(before)

    from paddle_tpu.profiler import metrics as _pm
    saves = delta.get("resilience.saves", 0)
    tokens = batch * seq * k * n_windows
    save_h = _pm.get_histogram("resilience.save_ms").summary()
    leg = {"fused_steps": k,
           "save_ms_p50": round(save_h["p50"], 2),
           "save_ms_p99": round(save_h["p99"], 2),
           "windows": n_windows,
           "async_saves": saves,
           "tokens_per_sec": round(tokens / max(ckpt_s, 1e-9), 2),
           "bare_tokens_per_sec": round(tokens / max(base_s, 1e-9), 2),
           "ckpt_overhead_frac": round(max(0.0, ckpt_s / max(base_s, 1e-9)
                                           - 1.0), 4),
           "save_ms_total": delta.get("resilience.save_ms", 0),
           "syncs": delta.get("jit.syncs", 0),
           "retraces": delta.get("jit.traces", 0),
           "rehydrates": delta.get("jit.hydrates", 0)}
    if leg["syncs"] != saves or leg["retraces"] or leg["rehydrates"]:
        raise AssertionError(
            f"checkpoint leg broke the one-sync-per-save budget: {leg}")
    del step, model, opt
    return leg


def _latency_ms(hist):
    """Compact p50/p95/p99 (+count/mean) in ms from an ns histogram."""
    s = hist.summary()
    return {"count": s["count"],
            "mean_ms": round(s["mean"] / 1e6, 3),
            "p50_ms": round(s["p50"] / 1e6, 3),
            "p95_ms": round(s["p95"] / 1e6, 3),
            "p99_ms": round(s["p99"] / 1e6, 3)}


def _run_serve_leg(cfg, n_requests=64, max_new=64, max_slots=8,
                   min_bucket=8, n_verify=8, seed=0):
    """Continuous-batching serving vs sequential generate.  The engine
    serves ``n_requests`` staggered mixed-length requests (its TTFT /
    inter-token-latency / queue-wait histograms give the leg's p50/p95/p99
    tail); the first ``n_verify`` of them are also run through sequential
    ``GPT.generate`` for the token-identity gate and the speedup baseline.
    Both paths are timed warm (one warm engine request per distinct
    prefill bucket); the engine run is two waves so late arrivals really
    do join slots mid-decode.  Returns the leg dict."""
    import paddle_tpu as paddle
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.profiler import counters
    from paddle_tpu.profiler import trace as rtrace
    from paddle_tpu.serving import LLMEngine
    from paddle_tpu.serving.engine import bucket_length

    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed)
    S = cfg.max_seq_len
    n_verify = min(n_verify, n_requests)
    lens = [int(rng.randint(max(2, S // 16), S - max_new))
            for _ in range(n_requests)]
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in lens]

    def seq_pass():
        return [np.asarray(model.generate(
            paddle.to_tensor(np.asarray([p])),
            max_new_tokens=max_new).numpy())[0]
            for p in prompts[:n_verify]]
    seq_pass()  # warm: one compiled generate program per prompt length
    t0 = time.perf_counter()
    seq_outs = seq_pass()
    seq_s = time.perf_counter() - t0

    eng = LLMEngine(model, max_slots=max_slots, max_seq_len=S,
                    min_bucket=min_bucket)
    # warm: one throwaway request per distinct prompt bucket (compiles
    # its prefill chunks) plus the decode program
    warm = [rng.randint(0, cfg.vocab_size,
                        size=min(b, S - 3)).tolist()
            for b in sorted({bucket_length(n, min_bucket, S)
                             for n in lens})]
    for _ in eng.generate(warm, max_new_tokens=2):
        pass
    warmed_counts = {n: h.count for n, h in eng.hists.items()}
    # measured pass runs fully traced (head sampling = keep all): the leg
    # reports WHERE the latency tail lives (queue vs prefill vs decode),
    # not just that it exists.  The parity gates elsewhere prove tracing
    # adds zero syncs/retraces, so tracing the timed pass is honest.
    rtrace.clear()
    _flags.set_flags({"FLAGS_request_trace_sample": 1.0})
    before = counters.snapshot()
    t0 = time.perf_counter()
    try:
        half = n_requests // 2
        hs = [eng.add_request(p, max_new_tokens=max_new)
              for p in prompts[:half]]
        for _ in range(3):
            eng.step()  # wave 1 decodes; wave 2 arrives mid-flight
        hs += [eng.add_request(p, max_new_tokens=max_new)
               for p in prompts[half:]]
        while not all(h.is_finished for h in hs):
            eng.step()
    finally:
        _flags.set_flags({"FLAGS_request_trace_sample": 0.0})
    serve_s = time.perf_counter() - t0
    delta = counters.delta(before)
    trace_block = {"sample": 1.0,
                   "kept": len(rtrace.kept_ids()),
                   "stages": rtrace.stage_breakdown()}

    match = all(np.array_equal(h.output_ids(), s)
                for h, s in zip(hs[:n_verify], seq_outs))
    serve_tps = n_requests * max_new / max(serve_s, 1e-9)
    seq_tps = n_verify * max_new / max(seq_s, 1e-9)
    snap = eng.histogram_snapshot()
    leg = {"requests": n_requests,
           "max_new_tokens": max_new,
           "max_slots": max_slots,
           "decode_tokens_per_sec": round(serve_tps, 2),
           "sequential_tokens_per_sec": round(seq_tps, 2),
           "serve_speedup": round(serve_tps / max(seq_tps, 1e-9), 4),
           "outputs_match_generate": match,
           "steady_retraces": delta.get("serving.retraces", 0),
           "prefill_programs": eng.stats()["prefill_programs"],
           "ttft": _latency_ms(snap["serving.ttft_ns"]),
           "itl": _latency_ms(snap["serving.itl_ns"]),
           "queue_wait": _latency_ms(snap["serving.queue_wait_ns"]),
           "trace": trace_block}
    # the tail stats must cover the measured request set, not just warmup
    measured = snap["serving.ttft_ns"].count \
        - warmed_counts["serving.ttft_ns"]
    if measured < n_requests:
        raise AssertionError(
            f"serving leg: TTFT histogram covered {measured} measured "
            f"requests, expected {n_requests}")
    if trace_block["kept"] < n_requests:
        raise AssertionError(
            f"serving leg: only {trace_block['kept']} request traces kept "
            f"at sample=1, expected {n_requests}")
    if not match:
        raise AssertionError(
            "serving leg: engine output diverged from sequential "
            "GPT.generate")
    leg["devicetime"] = _sampled_devicetime(
        lambda: [None for _ in eng.generate(prompts[:4],
                                            max_new_tokens=8)])
    del eng, model
    return leg


def _run_paged_leg(cfg, n_requests=64, max_new=64, max_slots=8,
                   min_bucket=8, block_size=16, prefill_chunk=256,
                   n_verify=8, seed=0):
    """What a block pool admits at a fixed KV HBM budget.

    Leg 1 (capacity): a mixed-length request set served by an engine
    whose block pool holds ``max_slots`` sequences of ``S_max``
    (``max_slots * ceil(S/bs)`` blocks).  Because requests reserve only
    the blocks they can actually touch, the pool admits several requests
    per ``S_max`` of KV — gated at ≥2× ``max_slots`` peak concurrent
    admitted requests.  The first ``n_verify`` requests are verified
    token-identical to sequential ``GPT.generate``.

    Leg 2 (shared prefix): ``n_requests`` prompts sharing one
    system-prompt prefix, served sequentially enough to feed the prefix
    tree — reports TTFT p50/p95 and gates ``prefix_hits > 0`` with
    strictly fewer prefill-chunk launches than a no-cache twin."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.profiler import counters
    from paddle_tpu.serving import LLMEngine
    from paddle_tpu.serving.kvcache import blocks_for_tokens

    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed)
    S = cfg.max_seq_len
    n_verify = min(n_verify, n_requests)
    lo = max(2, S // 16)
    hi = max(lo + 1, S // 4 - max_new)
    lens = [int(rng.randint(lo, hi)) for _ in range(n_requests)]
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in lens]
    refs = [np.asarray(model.generate(
        paddle.to_tensor(np.asarray([p])),
        max_new_tokens=max_new).numpy())[0] for p in prompts[:n_verify]]

    def serve(eng, ps):
        hs = [eng.add_request(p, max_new_tokens=max_new) for p in ps]
        peak = 0
        while not all(h.is_finished for h in hs):
            eng.step()
            peak = max(peak, eng.stats()["active"])
        return hs, peak

    # KV HBM = L x max_slots x S_max tokens; scheduling slots are
    # host-side bookkeeping, so the admitted concurrency is bounded by
    # memory, not by rows
    n_blocks = max_slots * blocks_for_tokens(S, block_size) + 1
    peng = LLMEngine(model, max_slots=4 * max_slots, max_seq_len=S,
                     min_bucket=min_bucket,
                     block_size=block_size, n_blocks=n_blocks,
                     prefill_chunk=prefill_chunk)
    # warm one request per power-of-two chunk bucket (+ the decode)
    b, pwarm = min_bucket, []
    while b <= peng.prefill_chunk:
        pwarm.append(rng.randint(0, cfg.vocab_size,
                                 size=min(b, S - 3)).tolist())
        b *= 2
    for _ in peng.generate(pwarm, max_new_tokens=2):
        pass
    pbefore = counters.snapshot()
    t0 = time.perf_counter()
    phs, paged_peak = serve(peng, prompts)
    paged_s = time.perf_counter() - t0
    pdelta = counters.delta(pbefore)
    paged_tps = n_requests * max_new / max(paged_s, 1e-9)
    for h, r in zip(phs[:n_verify], refs):
        if not np.array_equal(h.output_ids(), r):
            raise AssertionError(
                "paged leg: engine output diverged from generate")
    capacity_ratio = paged_peak / max_slots
    if capacity_ratio < 2.0:
        raise AssertionError(
            f"paged leg: peak concurrency {paged_peak} in the KV HBM of "
            f"{max_slots} sequences of S_max = {capacity_ratio:.2f}x "
            "(want >= 2x)")

    # shared-system-prompt workload: TTFT tail + prefix-cache economics.
    # The first request prefills the system prompt; it is finished (and
    # donated to the tree) before the rest arrive, so every later
    # request shares the cached prefix.
    bs = block_size
    sys_len = max(bs, (S // 4 // bs) * bs)
    tail_len = max(2, min(bs, S - sys_len - max_new - 2))
    sysp = rng.randint(0, cfg.vocab_size, size=sys_len).tolist()
    shared = [sysp + rng.randint(0, cfg.vocab_size,
                                 size=tail_len).tolist()
              for _ in range(n_requests)]

    def serve_shared(eng):
        h0 = eng.add_request(shared[0], max_new_tokens=max_new)
        while not h0.is_finished:
            eng.step()
        hs = [eng.add_request(p, max_new_tokens=max_new)
              for p in shared[1:]]
        while not all(h.is_finished for h in hs):
            eng.step()

    nc_eng = LLMEngine(model, max_slots=4 * max_slots, max_seq_len=S,
                       min_bucket=min_bucket,
                       block_size=block_size, n_blocks=n_blocks,
                       prefill_chunk=prefill_chunk, prefix_cache=False)
    ncbefore = counters.snapshot()
    serve_shared(nc_eng)
    nc_chunks = counters.delta(ncbefore).get("serving.kv.prefill_chunks",
                                             0)
    del nc_eng
    pc_eng = LLMEngine(model, max_slots=4 * max_slots, max_seq_len=S,
                       min_bucket=min_bucket,
                       block_size=block_size, n_blocks=n_blocks,
                       prefill_chunk=prefill_chunk)
    pcbefore = counters.snapshot()
    t0 = time.perf_counter()
    serve_shared(pc_eng)
    shared_s = time.perf_counter() - t0
    pcdelta = counters.delta(pcbefore)
    pc_chunks = pcdelta.get("serving.kv.prefill_chunks", 0)
    pc_hits = pcdelta.get("serving.kv.prefix_hits", 0)
    if pc_hits < n_requests - 1:
        raise AssertionError(
            f"paged leg: shared-prefix workload scored {pc_hits} "
            f"prefix hits (want >= {n_requests - 1})")
    if not pc_chunks < nc_chunks:
        raise AssertionError(
            f"paged leg: prefix cache launched {pc_chunks} prefill "
            f"chunks vs {nc_chunks} without (want strictly fewer)")
    snap = pc_eng.histogram_snapshot()
    pstats = pc_eng.stats()
    leg = {"requests": n_requests,
           "max_new_tokens": max_new,
           "block_size": block_size,
           "n_blocks": n_blocks,
           "prefill_chunk": peng.prefill_chunk,
           "kv_hbm_slots_equiv": max_slots,
           "peak_concurrent_paged": paged_peak,
           "capacity_ratio": round(capacity_ratio, 3),
           "decode_tokens_per_sec_paged": round(paged_tps, 2),
           "steady_retraces": pdelta.get("serving.retraces", 0),
           "outputs_match_generate": True,
           "shared_prefix": {
               "requests": n_requests,
               "system_prompt_tokens": sys_len,
               "prefix_hits": pc_hits,
               "prefix_hit_tokens": pcdelta.get(
                   "serving.kv.prefix_hit_tokens", 0),
               "prefill_chunks": pc_chunks,
               "prefill_chunks_nocache": nc_chunks,
               "wall_s": round(shared_s, 3),
               "ttft": _latency_ms(snap["serving.ttft_ns"]),
               "itl": _latency_ms(snap["serving.itl_ns"]),
               "block_occupancy_p95": round(
                   snap["serving.kv.block_occupancy"].percentile(95),
                   4)},
           "blocks_evicted": pstats["blocks_evicted"],
           "cow_copies": pstats["cow_copies"]}
    leg["devicetime"] = _sampled_devicetime(
        lambda: [None for _ in pc_eng.generate(prompts[:4],
                                               max_new_tokens=8)])
    del peng, pc_eng, model
    return leg


def _run_paged_q_leg(cfg, n_requests=64, max_new=64, max_slots=4,
                     min_bucket=8, block_size=16, prefill_chunk=256,
                     kv_dtype="int8", n_verify=4, seed=0):
    """Quantized-KV capacity leg: an ``kv_dtype`` paged engine vs the
    model-dtype paged baseline at the SAME KV HBM byte budget.

    The baseline pool is sized like the paged leg's
    (``max_slots * ceil(S/bs)`` blocks of the model dtype); the
    quantized pool gets ``floor(budget / quant_block_bytes)`` blocks
    where a quantized block costs 1 byte/value plus the per-token fp32
    scale rows (8 bytes per token across K and V).  Both engines serve
    the same memory-bound workload (identical-length prompts, scheduling
    slots ample, so admission is bounded by pool bytes alone) — gated at
    >= 2x peak concurrent admitted requests with zero steady retraces.
    Decode tok/s and TTFT/ITL are reported for both, with a >=0.9x
    decode parity gate.  Token identity of the quantized
    engine is gated in tests/ and scripts/check_counters.py on the tiny
    model; here the baseline engine is verified against ``generate`` and
    the quantized match count is reported."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.kernels.paged_attention import KV_DTYPES
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.profiler import counters
    from paddle_tpu.serving import LLMEngine
    from paddle_tpu.serving.kvcache import blocks_for_tokens

    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed)
    S = cfg.max_seq_len
    L, nh = cfg.num_layers, cfg.num_heads
    hd = cfg.hidden_size // nh
    bs = block_size
    dt = jnp.dtype(cfg.dtype)
    adt = jnp.dtype(KV_DTYPES[kv_dtype])
    # the fixed byte budget: the baseline pool's K+V arena
    raw_block = 2 * L * bs * nh * hd * dt.itemsize
    q_block = 2 * L * bs * nh * hd * adt.itemsize + 2 * L * bs * 4
    n_blocks_raw = max_slots * blocks_for_tokens(S, bs) + 1
    budget = n_blocks_raw * raw_block
    n_blocks_q = int(budget // q_block)

    plen = max(2, S // 8)
    prompts = [rng.randint(0, cfg.vocab_size, size=plen).tolist()
               for _ in range(n_requests)]
    n_verify = min(n_verify, n_requests)
    refs = [np.asarray(model.generate(
        paddle.to_tensor(np.asarray([p])),
        max_new_tokens=max_new).numpy())[0] for p in prompts[:n_verify]]

    def engine(n_blocks, **kw):
        eng = LLMEngine(model, max_slots=n_requests, max_seq_len=S,
                        min_bucket=min_bucket,
                        block_size=bs, n_blocks=n_blocks,
                        prefill_chunk=prefill_chunk, prefix_cache=False,
                        **kw)
        b, pwarm = min_bucket, []
        while b <= eng.prefill_chunk:
            pwarm.append(rng.randint(0, cfg.vocab_size,
                                     size=min(b, S - 3)).tolist())
            b *= 2
        for _ in eng.generate(pwarm, max_new_tokens=2):
            pass
        return eng

    def serve(eng):
        hs = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
        peak = 0
        t0 = time.perf_counter()
        while not all(h.is_finished for h in hs):
            eng.step()
            peak = max(peak, eng.stats()["active"])
        return hs, peak, time.perf_counter() - t0

    beng = engine(n_blocks_raw)
    bhs, raw_peak, raw_s = serve(beng)
    raw_tps = n_requests * max_new / max(raw_s, 1e-9)
    for h, r in zip(bhs[:n_verify], refs):
        if not np.array_equal(h.output_ids(), r):
            raise AssertionError(
                "paged_q leg: baseline paged output diverged from "
                "generate")
    raw_snap = beng.histogram_snapshot()
    del beng

    qeng = engine(n_blocks_q, kv_dtype=kv_dtype)
    qbefore = counters.snapshot()
    qhs, q_peak, q_s = serve(qeng)
    qdelta = counters.delta(qbefore)
    q_tps = n_requests * max_new / max(q_s, 1e-9)
    q_match = sum(int(np.array_equal(h.output_ids(), r))
                  for h, r in zip(qhs[:n_verify], refs))
    capacity_ratio = q_peak / max(1, raw_peak)
    if capacity_ratio < 2.0:
        raise AssertionError(
            f"paged_q leg: {kv_dtype} peak concurrency {q_peak} vs "
            f"{dt.name} {raw_peak} = {capacity_ratio:.2f}x at the same "
            "KV HBM byte budget (want >= 2x)")
    if qdelta.get("serving.retraces", 0):
        raise AssertionError(
            f"paged_q leg: {qdelta['serving.retraces']} steady retraces "
            "on the quantized engine (want 0)")
    decode_parity = q_tps / max(raw_tps, 1e-9)
    if decode_parity < 0.9:
        raise AssertionError(
            f"paged_q leg: quantized decode {q_tps:.1f} tok/s vs "
            f"baseline {raw_tps:.1f} = {decode_parity:.2f}x (want >= "
            "0.9x on TPU)")
    q_snap = qeng.histogram_snapshot()
    leg = {"kv_dtype": kv_dtype,
           "requests": n_requests,
           "max_new_tokens": max_new,
           "prompt_tokens": plen,
           "block_size": bs,
           "kv_hbm_budget_bytes": int(budget),
           "n_blocks_raw": n_blocks_raw,
           "n_blocks_quant": n_blocks_q,
           "block_bytes_raw": raw_block,
           "block_bytes_quant": q_block,
           "arena_bytes_quant": counters.get(
               "serving.kv.quant.arena_bytes"),
           "bytes_saved_vs_same_blocks": counters.get(
               "serving.kv.quant.bytes_saved"),
           "peak_concurrent_raw": raw_peak,
           "peak_concurrent_quant": q_peak,
           "capacity_ratio": round(capacity_ratio, 3),
           "decode_tokens_per_sec_raw": round(raw_tps, 2),
           "decode_tokens_per_sec_quant": round(q_tps, 2),
           "decode_parity": round(decode_parity, 4),
           "steady_retraces": qdelta.get("serving.retraces", 0),
           "quant_tokens": qdelta.get("serving.kv.quant.prefill_tokens",
                                      0)
           + qdelta.get("serving.kv.quant.decode_tokens", 0),
           "verified_match_raw": n_verify,
           "verified_match_quant": f"{q_match}/{n_verify}",
           "ttft_raw": _latency_ms(raw_snap["serving.ttft_ns"]),
           "ttft_quant": _latency_ms(q_snap["serving.ttft_ns"]),
           "itl_raw": _latency_ms(raw_snap["serving.itl_ns"]),
           "itl_quant": _latency_ms(q_snap["serving.itl_ns"])}
    del qeng, model
    return leg


def _run_spec_leg(n_requests=16, max_new=32, max_slots=4, min_bucket=8,
                  block_size=16, prefill_chunk=64, spec_k=4, hidden=512,
                  layers=12, draft_layers=1, vocab=512, seq_len=256,
                  seed=0, min_speedup=1.3):
    """Speculative-decoding leg: draft/verify engine vs the non-spec
    paged baseline on the same greedy workload.

    The model pair is ALIGNED by construction: both share the embedding /
    final-norm weights and every transformer block's matmul weights are
    zeroed (a zero block contributes nothing to the residual stream but
    still costs its full matmul FLOPs/bytes), so draft and target emit
    the same greedy chain and acceptance sits at ~1.0 — the leg measures
    the MACHINERY's ceiling (one [B, K+1] verify amortizes the target's
    weight sweep over up to K+1 tokens) rather than any particular
    trained draft's acceptance.  The target is many zeroed layers deep so
    its weight sweep dominates; the draft is ``draft_layers`` of the same
    width.

    Gates: speculative greedy output token-identical to the baseline
    engine; zero steady-state retraces over the measured window;
    ``accepted + rejected == drafted``; net decode tok/s >=
    ``min_speedup`` x the baseline."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.profiler import counters
    from paddle_tpu.serving import LLMEngine
    from paddle_tpu.serving.kvcache import blocks_for_tokens

    def build(n_layers, seed_):
        cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                        num_layers=n_layers, num_heads=8,
                        max_seq_len=seq_len, use_rope=True,
                        use_flash_attention=False, dtype="float32")
        paddle.seed(seed_)
        m = GPTForCausalLM(cfg)
        m.eval()
        for n in ("qkv_w", "qkv_b", "proj_w", "proj_b",
                  "fc1_w", "fc1_b", "fc2_w", "fc2_b"):
            p = getattr(m, n)
            p._data = jnp.zeros_like(p._data)
        return m

    target = build(layers, seed)
    draft = build(draft_layers, seed + 1)
    for n in ("wte", "lnf_w", "lnf_b"):
        getattr(draft, n)._data = getattr(target, n)._data

    rng = np.random.RandomState(seed)
    plen = max(2, seq_len // 8)
    prompts = [rng.randint(0, vocab, size=plen).tolist()
               for _ in range(n_requests)]
    n_blocks = 2 * max_slots * blocks_for_tokens(seq_len, block_size) + 1

    def engine(**kw):
        eng = LLMEngine(target, max_slots=max_slots, max_seq_len=seq_len,
                        min_bucket=min_bucket,
                        block_size=block_size, n_blocks=n_blocks,
                        prefill_chunk=prefill_chunk, prefix_cache=False,
                        **kw)
        b, pwarm = min_bucket, []
        while b <= eng.prefill_chunk:
            pwarm.append(rng.randint(0, vocab,
                                     size=min(b, seq_len - 3)).tolist())
            b *= 2
        for _ in eng.generate(pwarm, max_new_tokens=2):
            pass
        return eng

    def serve(eng):
        hs = [eng.add_request(p, max_new_tokens=max_new, seed=i)
              for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        while not all(h.is_finished for h in hs):
            eng.step()
        return hs, time.perf_counter() - t0

    beng = engine()
    bhs, base_s = serve(beng)
    base_tps = n_requests * max_new / max(base_s, 1e-9)
    base_snap = beng.histogram_snapshot()
    del beng

    seng = engine(draft_model=draft, spec_k=spec_k)
    before = counters.snapshot()
    shs, spec_s = serve(seng)
    delta = counters.delta(before)
    spec_tps = n_requests * max_new / max(spec_s, 1e-9)
    for b, s in zip(bhs, shs):
        if b.tokens != s.tokens:
            raise AssertionError(
                "spec leg: speculative greedy output diverged from the "
                "non-speculative paged engine")
    if delta.get("serving.retraces", 0):
        raise AssertionError(
            f"spec leg: {delta['serving.retraces']} steady retraces on "
            "the speculative engine (want 0)")
    drafted = delta.get("serving.spec.drafted", 0)
    accepted = delta.get("serving.spec.accepted", 0)
    rejected = delta.get("serving.spec.rejected", 0)
    if accepted + rejected != drafted:
        raise AssertionError(
            f"spec leg: accepted {accepted} + rejected {rejected} != "
            f"drafted {drafted}")
    speedup = spec_tps / max(base_tps, 1e-9)
    if speedup < min_speedup:
        raise AssertionError(
            f"spec leg: speculative decode {spec_tps:.1f} tok/s vs "
            f"baseline {base_tps:.1f} = {speedup:.2f}x (want >= "
            f"{min_speedup}x)")
    spec_snap = seng.histogram_snapshot()
    st = seng.stats()
    leg = {"spec_k": spec_k,
           "requests": n_requests,
           "max_new_tokens": max_new,
           "prompt_tokens": plen,
           "target_layers": layers,
           "draft_layers": draft_layers,
           "hidden": hidden,
           "drafted": drafted,
           "accepted": accepted,
           "rejected": rejected,
           "acceptance_rate": round(accepted / max(1, drafted), 4),
           "acceptance_ema": st["spec_acceptance_ema"],
           "yield_ema": round(st["spec_yield_ema"], 3),
           "verify_steps": delta.get("serving.spec.verify_steps", 0),
           "draft_steps": delta.get("serving.spec.draft_steps", 0),
           "rollback_blocks": delta.get("serving.spec.rollback_blocks",
                                        0),
           "steady_retraces": delta.get("serving.retraces", 0),
           "decode_tokens_per_sec_base": round(base_tps, 2),
           "decode_tokens_per_sec_spec": round(spec_tps, 2),
           "spec_speedup": round(speedup, 4),
           "ttft_base": _latency_ms(base_snap["serving.ttft_ns"]),
           "ttft_spec": _latency_ms(spec_snap["serving.ttft_ns"]),
           "itl_base": _latency_ms(base_snap["serving.itl_ns"]),
           "itl_spec": _latency_ms(spec_snap["serving.itl_ns"])}
    leg["devicetime"] = _sampled_devicetime(
        lambda: [None for _ in seng.generate(prompts[:4],
                                             max_new_tokens=8)])
    del seng, target, draft
    return leg


def _run_fleet_leg(cfg, replicas=2, n_requests=8, max_new=32, max_slots=4,
                   min_bucket=8, seed=0):
    """Elastic-fleet leg: the same seeded request set through a
    multi-replica ``ServingFleet`` twice — clean, then with one replica
    killed mid-decode (deterministic ``replica_crash`` on the first
    request).  Reports aggregate decode tokens/s for both runs and the
    churn retention fraction, and gates the durability invariants: zero
    lost requests, respawns == injected kills, and the churn output
    token-identical to the clean run (same seeds → same streams, replayed
    across the respawn)."""
    import urllib.request

    import paddle_tpu as paddle
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.profiler import counters
    from paddle_tpu.profiler import trace as rtrace
    from paddle_tpu.profiler.ops import OpsServer
    from paddle_tpu.resilience import faultinject
    from paddle_tpu.serving import ServingFleet

    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed)
    S = cfg.max_seq_len
    lens = [int(rng.randint(max(2, S // 16), S - max_new))
            for _ in range(n_requests)]
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in lens]
    seeds = list(range(100, 100 + n_requests))

    fleet = ServingFleet(model, replicas=replicas, max_slots=max_slots,
                         max_seq_len=S, min_bucket=min_bucket,
                         threaded=False, warm_buckets=lens)

    def run_pass(kill=False):
        before = counters.snapshot()
        t0 = time.perf_counter()
        hs = [fleet.submit(p, max_new_tokens=max_new, seed=s)
              for p, s in zip(prompts, seeds)]
        if kill:
            with faultinject.fault_schedule(
                    f"replica_crash@{hs[0].rid}"):
                fleet.join(hs)
        else:
            fleet.join(hs)
        dt = time.perf_counter() - t0
        return hs, dt, counters.delta(before)

    run_pass()  # warm timing pass (programs already compiled at spawn)
    # both measured passes run traced: the churn pass's respawned request
    # keeps ONE trace_id across replicas, so the breakdown sees the full
    # redispatch story, not two half-requests
    rtrace.clear()
    _flags.set_flags({"FLAGS_request_trace_sample": 1.0})
    try:
        clean_hs, clean_s, clean_d = run_pass()
        churn_hs, churn_s, churn_d = run_pass(kill=True)
    finally:
        _flags.set_flags({"FLAGS_request_trace_sample": 0.0})
    # fleet-wide latency tail: replica histograms merged by the router
    # (dead replicas included — their delivered latency counts)
    agg = fleet.router.aggregate_histograms(fleet._replicas)
    obs = fleet.router.observability_summary(fleet._replicas)
    # ops-endpoint smoke: the live process plane serves this very fleet
    # over HTTP while it is still up (ephemeral port, stdlib client)
    with OpsServer(fleet=fleet) as srv:
        with urllib.request.urlopen(srv.url("/healthz"), timeout=10) as r:
            ops_health = json.loads(r.read())
        with urllib.request.urlopen(srv.url("/traces"), timeout=10) as r:
            ops_traces = json.loads(r.read())
    fleet.drain()

    match = all(c.finish_reason == "length" and k.finish_reason == "length"
                and c.tokens == k.tokens
                for c, k in zip(clean_hs, churn_hs))
    decode_tokens = n_requests * max_new
    clean_tps = decode_tokens / max(clean_s, 1e-9)
    churn_tps = decode_tokens / max(churn_s, 1e-9)
    leg = {"replicas": replicas,
           "requests": n_requests,
           "max_new_tokens": max_new,
           "decode_tokens_per_sec": round(clean_tps, 2),
           "churn_decode_tokens_per_sec": round(churn_tps, 2),
           "churn_retention": round(churn_tps / max(clean_tps, 1e-9), 4),
           "respawns": churn_d.get("serving.fleet.respawns", 0),
           "retried": churn_d.get("serving.fleet.retried", 0),
           "lost": churn_d.get("serving.fleet.lost", 0),
           "replayed_tokens": churn_d.get("serving.fleet.replayed_tokens",
                                          0),
           "steady_retraces": clean_d.get("serving.retraces", 0),
           "outputs_match_clean": match,
           "ttft": _latency_ms(agg["serving.ttft_ns"]),
           "itl": _latency_ms(agg["serving.itl_ns"]),
           "queue_wait": _latency_ms(agg["serving.queue_wait_ns"]),
           "trace": {"kept": obs["traces_kept"],
                     "stages": obs["stage_breakdown"]},
           "ops": {"healthz": ops_health.get("status"),
                   "alive": (ops_health.get("fleet") or {}).get("alive"),
                   "traces_kept": ops_traces.get("count")}}
    if (not match or leg["lost"] != 0 or leg["respawns"] != 1
            or leg["retried"] < 1 or leg["steady_retraces"] != 0):
        raise AssertionError(
            f"fleet leg broke the durability invariants: {leg}")
    if ops_health.get("status") != "ok" or not ops_traces.get("count"):
        raise AssertionError(
            f"fleet leg: live ops endpoint unhealthy or trace-blind: "
            f"{leg['ops']}")
    del fleet, model
    return leg


def _run_multitenant_leg(cfg, replicas=2, tenants=6, adapter_slots=4,
                         rank=8, n_requests=12, max_new=32, max_slots=4,
                         min_bucket=8, block_size=16, prefill_chunk=None,
                         seed=0):
    """Multi-tenant LoRA serving leg: ``tenants`` adapters through a
    ``replicas``-replica fleet whose per-replica AdapterArena holds only
    ``adapter_slots`` of them, so cold tenants page in on demand and the
    LRU evicts idle ones — many model variants at the HBM cost of a few.
    Two measured passes: FAIR (tenants round-robin with base rows mixed
    in) and NOISY (tenant 0 floods the fleet while the others get one
    request each, plus an injected ``adapter_load_drop`` on one
    admission).  Reports decode tokens/s for both, per-tenant-bucket
    TTFT/ITL tails from the router-merged histograms, the noisy pass's
    flood-bucket ITL-p95 skew, arena traffic (loads / evictions /
    resident / bytes) and the router's tenant-affinity wins; gates zero
    lost requests, the dropped load recovering to a finished
    token-identical request, paging genuinely exercised (loads AND
    evictions move), and the fair pass token-identical across repeats
    with ZERO steady retraces — one compiled decode program serves every
    tenant mix."""
    import zlib

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.profiler import counters
    from paddle_tpu.resilience import faultinject
    from paddle_tpu.serving import ServingFleet
    from paddle_tpu.serving.adapters import random_lora_factors

    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed)
    S = cfg.max_seq_len
    lens = [int(rng.randint(max(2, S // 16), S - max_new))
            for _ in range(n_requests)]
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in lens]
    seeds = list(range(100, 100 + n_requests))
    names = [f"tenant{i}" for i in range(tenants)]
    # fair mix: tenants round-robin, every (tenants+1)-th row base; noisy
    # mix: tenant 0 floods, every other tenant trickles one request, and
    # the LAST row is a tenant no pass has touched — its admission MUST
    # page in, so the adapter_load_drop scheduled on it always fires
    cold = "coldspare"
    fair = [None if i % (tenants + 1) == tenants
            else names[i % (tenants + 1)] for i in range(n_requests)]
    noisy = ([names[0]] * (n_requests - tenants)) + names[1:] + [cold]

    fleet = ServingFleet(model, replicas=replicas, max_slots=max_slots,
                         max_seq_len=S, min_bucket=min_bucket,
                         threaded=False, warm_buckets=lens,
                         block_size=block_size,
                         prefill_chunk=prefill_chunk,
                         adapter_slots=adapter_slots, adapter_rank=rank)
    for i, t in enumerate(names + [cold]):
        fleet.register_adapter(
            t, random_lora_factors(cfg, rank, seed=10 + i, scale=0.05))

    def run_pass(mix, drop_on_last=False):
        before = counters.snapshot()
        t0 = time.perf_counter()
        hs = [fleet.submit(p, max_new_tokens=max_new, seed=s, adapter=t)
              for p, s, t in zip(prompts, seeds, mix)]
        if drop_on_last:
            # the engine-side load fires at admission inside pump(), so
            # scheduling after submit still intercepts it
            with faultinject.fault_schedule(
                    f"adapter_load_drop@{hs[-1]._er.rid}"):
                fleet.join(hs)
                fired = [s for s, _ in faultinject.fired]
        else:
            fleet.join(hs)
            fired = []
        dt = time.perf_counter() - t0
        return hs, dt, counters.delta(before), fired

    run_pass(fair)  # warm pass: programs compiled, tenants paged once
    warm_hs, _, _, _ = run_pass(fair)  # identity reference (same seeds)
    fair_hs, fair_s, fair_d, _ = run_pass(fair)
    hist_mark = fleet.router.aggregate_histograms(fleet._replicas)
    noisy_hs, noisy_s, noisy_d, fired = run_pass(noisy, drop_on_last=True)
    agg = fleet.router.aggregate_histograms(fleet._replicas)
    stats = fleet.stats()
    fleet.drain()

    match = all(f.finish_reason == "length" and f.tokens == w.tokens
                for f, w in zip(fair_hs, warm_hs))
    drop_ok = (noisy_hs[-1].finish_reason == "length"
               and "adapter_load_drop" in fired)
    # per-tenant-bucket tails (cumulative) + the noisy pass's windowed
    # flood-bucket skew: flood p95 vs the median p95 of the other buckets
    n_buckets = fleet._replicas[0].engine.tenant_buckets
    flood = f"t{zlib.crc32(names[0].encode()) % n_buckets}"
    per_tenant = {
        name.rsplit(".", 1)[-1]: _latency_ms(h)
        for name, h in sorted(agg.items())
        if name.startswith("serving.itl_ns.tenant.")}
    win, skew = {}, None
    for name, h in agg.items():
        if name.startswith("serving.itl_ns.tenant."):
            prev = hist_mark.get(name)
            d = h.delta(prev) if prev is not None else h
            if d.count >= 8:
                win[name.rsplit(".", 1)[-1]] = d.percentile(95)
    others = sorted(v for k, v in win.items() if k != flood)
    if flood in win and others:
        skew = round(win[flood] / max(others[len(others) // 2], 1e-9), 3)
    ad = stats["adapters"]
    decode_tokens = n_requests * max_new
    fair_tps = decode_tokens / max(fair_s, 1e-9)
    noisy_tps = decode_tokens / max(noisy_s, 1e-9)
    leg = {"replicas": replicas,
           "tenants": tenants,
           "adapter_slots_per_replica": adapter_slots,
           "adapter_rank": rank,
           "requests": n_requests,
           "max_new_tokens": max_new,
           "decode_tokens_per_sec": round(fair_tps, 2),
           "noisy_decode_tokens_per_sec": round(noisy_tps, 2),
           "tenants_per_slot": round(tenants / adapter_slots, 2),
           "arena_bytes": ad["arena_bytes"],
           "resident": ad["resident"],
           "loads": ad["loads"],
           "evictions": ad["evictions"],
           "exhausted_defers": ad["exhausted"],
           "load_drops": ad["load_drops"],
           "adapter_routed": ad["routed"],
           "steady_retraces": fair_d.get("serving.retraces", 0),
           "outputs_match_warm": match,
           "noisy_itl_p95_skew": skew,
           "ttft": _latency_ms(agg["serving.ttft_ns"]),
           "itl": _latency_ms(agg["serving.itl_ns"]),
           "per_tenant_itl": per_tenant}
    leg["lost"] = (fair_d.get("serving.fleet.lost", 0)
                   + noisy_d.get("serving.fleet.lost", 0))
    if (not match or not drop_ok or leg["steady_retraces"] != 0
            or leg["lost"] != 0 or leg["loads"] < tenants
            or leg["evictions"] < 1):
        raise AssertionError(
            f"multitenant leg broke the adapter-serving invariants: {leg}")
    del fleet, model
    return leg


def _run_disagg_leg(cfg, n_long=6, n_short=18, max_new=16, max_slots=None,
                    min_bucket=8, block_size=8, prefill_chunk=16,
                    min_speedup=1.3, seed=0):
    """Disaggregated prefill/decode leg: the same mixed long/short
    request set through a 2-replica unified paged fleet and a 1+1
    prefill/decode split at EQUAL replica count.  On the split, every
    prompt prefills on the prefill replica and hands its KV to the
    decode replica by block-granular migration, so long-prompt prefill
    chunks stop interleaving with the decode iterations of streams
    already emitting tokens — the classic interference that owns the
    unified fleet's p95 inter-token latency under mixed traffic.

    Gates: disagg p95 ITL beats unified by >= ``min_speedup`` (the
    headline number), disagg output token-identical to unified, every
    request migrated exactly once, zero steady retraces on BOTH roles in
    BOTH modes (the one-decode-program economics survive the split), and
    a churn pass with a migration severed mid-flight (``kv_migrate_drop``)
    plus a replica killed mid-stream: zero lost requests, output
    token-identical to the clean disagg pass."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.profiler import counters, metrics
    from paddle_tpu.resilience import faultinject
    from paddle_tpu.serving import ServingFleet

    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed)
    S = cfg.max_seq_len
    long_lens = [int(rng.randint(int(S * 0.7), S - max_new))
                 for _ in range(n_long)]
    short_lens = [int(rng.randint(4, max(5, S // 8)))
                  for _ in range(n_short)]
    # interleave so short streams are mid-decode while long prefills
    # arrive — the interference the split is supposed to remove
    lens = []
    si = iter(short_lens)
    ratio = max(1, n_short // n_long)
    for n in long_lens:
        lens.extend(itertools.islice(si, ratio))
        lens.append(n)
    lens.extend(si)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in lens]
    # the warm pass runs DISJOINT prompts of the same lengths: it
    # compiles every program (prefill buckets, decode, the migration
    # gather) without seeding the prefix trees with the measured
    # prompts — a warm-pass prefix hit would erase the very prefill
    # work whose interference this leg measures
    warm_prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
                    for n in lens]
    seeds = list(range(100, 100 + len(prompts)))
    if max_slots is None:
        # slots cover the whole burst on every replica: the comparison
        # isolates prefill/decode interference, not slot queueing (the
        # decode side of the split hosts ALL streams at once)
        max_slots = len(prompts)

    def build(prefill_replicas):
        # threaded: each replica gets its own scheduler thread, so the
        # split actually removes interference — a single shared loop
        # would serialize prefill chunks with decode steps regardless
        # of role assignment
        return ServingFleet(
            model, replicas=2, prefill_replicas=prefill_replicas,
            max_slots=max_slots, max_seq_len=S, min_bucket=min_bucket,
            threaded=True, block_size=block_size,
            n_blocks=max(128, 4 * S // block_size * max_slots),
            prefill_chunk=prefill_chunk, warm_buckets=lens,
            max_retries=2)

    def run_pass(fleet, schedule=None, which=None):
        before = counters.snapshot()
        t0 = time.perf_counter()
        hs = [fleet.submit(p, max_new_tokens=max_new, seed=s)
              for p, s in zip(which if which is not None else prompts,
                              seeds)]
        if schedule:
            with faultinject.fault_schedule(schedule):
                fleet.join(hs)
        else:
            fleet.join(hs)
        dt = time.perf_counter() - t0
        return hs, dt, counters.delta(before)

    def measure(prefill_replicas, schedule=None, rounds=1):
        fleet = build(prefill_replicas)
        # warm pass (disjoint prompts): compiles the migrate program too
        run_pass(fleet, which=warm_prompts)
        # fresh per-engine histograms so the fleet percentiles below see
        # ONLY the measured rounds (warmup + warm-pass latency excluded)
        for rep in fleet._replicas:
            rep.engine.hists = {
                n: metrics.Histogram(n, h.unit)
                for n, h in rep.engine.hists.items()}
        before = counters.snapshot()
        hs = d1 = None
        total_s = 0.0
        for r in range(rounds):
            if r:
                # later rounds stay prefill-cold: drop the prefix blocks
                # the previous round donated, or every repeat would be a
                # prefix hit and skip the very work being measured
                for rep in fleet._replicas:
                    if rep.engine.prefix is not None:
                        rep.engine.prefix.clear()
            rhs, dt, d = run_pass(fleet, schedule=schedule)
            total_s += dt
            if hs is None:
                hs, d1 = rhs, d
            elif any(a.tokens != b.tokens for a, b in zip(rhs, hs)):
                raise AssertionError(
                    "disagg leg: identical seeds diverged across "
                    "measured rounds")
        d = counters.delta(before)
        # block economics come from the cold first round; retrace /
        # loss / migration-count gates cover every round
        d["serving.fleet.migrate.blocks_copied"] = d1.get(
            "serving.fleet.migrate.blocks_copied", 0)
        d["serving.fleet.migrate.blocks_shared"] = d1.get(
            "serving.fleet.migrate.blocks_shared", 0)
        agg = fleet.router.aggregate_histograms(fleet._replicas)
        roles = fleet.stats()["roles"]
        fleet.drain()
        return hs, total_s, d, agg, roles

    rounds = 3
    uni_hs, uni_s, uni_d, uni_agg, _ = measure(0, rounds=rounds)
    dis_hs, dis_s, dis_d, dis_agg, roles = measure(1, rounds=rounds)
    match = all(u.finish_reason == "length" and v.finish_reason == "length"
                and u.tokens == v.tokens
                for u, v in zip(uni_hs, dis_hs))
    # churn: one migration severed between export and adopt plus one
    # replica crash while hand-offs are in flight — replay must deliver
    # the identical streams with nothing lost
    # rids count per-fleet: the churn fleet's warm pass consumes
    # 0..len-1, so the measured pass starts at rid == len(prompts)
    churn_hs, _, churn_d, _, _ = measure(
        1, schedule=(f"kv_migrate_drop@{len(prompts)}"
                     f",replica_crash@{len(prompts) + 1}"))
    churn_match = all(v.finish_reason == "length" and c.tokens == v.tokens
                      for c, v in zip(churn_hs, dis_hs))
    uni_itl = _latency_ms(uni_agg["serving.itl_ns"])
    dis_itl = _latency_ms(dis_agg["serving.itl_ns"])
    speedup = uni_itl["p95_ms"] / max(dis_itl["p95_ms"], 1e-9)
    decode_tokens = len(prompts) * max_new * rounds
    leg = {"replicas": 2,
           "roles": roles,
           "requests": len(prompts),
           "measured_rounds": rounds,
           "long_prompts": n_long,
           "max_new_tokens": max_new,
           "unified_itl": uni_itl,
           "disagg_itl": dis_itl,
           "itl_p95_speedup": round(speedup, 4),
           "unified_ttft": _latency_ms(uni_agg["serving.ttft_ns"]),
           "disagg_ttft": _latency_ms(dis_agg["serving.ttft_ns"]),
           "unified_decode_tokens_per_sec":
               round(decode_tokens / max(uni_s, 1e-9), 2),
           "disagg_decode_tokens_per_sec":
               round(decode_tokens / max(dis_s, 1e-9), 2),
           "migrated": dis_d.get("serving.fleet.migrate.requests", 0),
           "blocks_copied":
               dis_d.get("serving.fleet.migrate.blocks_copied", 0),
           "blocks_shared":
               dis_d.get("serving.fleet.migrate.blocks_shared", 0),
           "migrate_deferred":
               dis_d.get("serving.fleet.migrate.deferred", 0),
           "steady_retraces_unified": uni_d.get("serving.retraces", 0),
           "steady_retraces_disagg": dis_d.get("serving.retraces", 0),
           "outputs_match_unified": match,
           "churn": {
               "dropped": churn_d.get("serving.fleet.migrate.dropped", 0),
               "deaths": churn_d.get("serving.fleet.replica_deaths", 0),
               "retried": churn_d.get("serving.fleet.retried", 0),
               "lost": churn_d.get("serving.fleet.lost", 0),
               "outputs_match_clean": churn_match}}
    if (not match or leg["migrated"] != len(prompts) * rounds
            or leg["steady_retraces_unified"] != 0
            or leg["steady_retraces_disagg"] != 0
            or uni_d.get("serving.fleet.lost", 0) != 0
            or dis_d.get("serving.fleet.lost", 0) != 0):
        raise AssertionError(
            f"disagg leg broke the migration invariants: {leg}")
    if (not churn_match or leg["churn"]["lost"] != 0
            or leg["churn"]["dropped"] < 1 or leg["churn"]["deaths"] < 1):
        raise AssertionError(
            f"disagg leg churn pass broke durability: {leg}")
    if speedup < min_speedup:
        raise AssertionError(
            f"disagg p95 ITL speedup {speedup:.3f}x below the "
            f"{min_speedup:.2f}x floor: {leg}")
    del model
    return leg


def _run_tiered_leg(cfg, n_sessions=24, max_new=64, max_slots=8,
                    min_bucket=8, block_size=16, prefill_chunk=256,
                    n_verify=4, seed=0, min_retention=0.5):
    """Host-RAM KV tier under 2x/4x oversubscribed device KV.

    Two-pass session traffic (every prompt queried twice — the second
    visit wants its first visit's KV back) served on identical prompts by
    three paged engines: a base whose block pool holds the whole working
    set, and two whose pools are cut to 1/2 and 1/4 of it with a pinned
    host tier sized to cover the difference.  Under oversubscription the
    radix tree's cold leaves spill to host buffers instead of being
    freed, and pass 2 restores them instead of re-prefilling.  Gates:
    token identity to sequential ``generate`` on every engine, every
    request reaching length/eos (zero sheds/errors under pressure),
    spill AND restore traffic actually flowing at 2x, and 2x decode
    tok/s >= ``min_retention`` of the base.  A 2-replica tiered fleet
    then replays the same traffic, gating prefix-affinity routing wins
    (``serving.fleet.prefix_routed`` — the router prices host-resident
    prefixes too) and the zero-lost / zero-shed invariants."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.profiler import counters
    from paddle_tpu.serving import LLMEngine, ServingFleet
    from paddle_tpu.serving.kvcache import blocks_for_tokens

    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed)
    S = cfg.max_seq_len
    bs = block_size
    n_verify = min(n_verify, n_sessions)
    lo = max(2, S // 16)
    hi = max(lo + 1, S // 8)
    lens = [int(rng.randint(lo, hi)) for _ in range(n_sessions)]
    sessions = [rng.randint(0, cfg.vocab_size, size=n).tolist()
                for n in lens]
    refs = [np.asarray(model.generate(
        paddle.to_tensor(np.asarray([p])),
        max_new_tokens=max_new).numpy())[0] for p in sessions[:n_verify]]

    # the device working set: every session's full sequence resident
    demand = sum(blocks_for_tokens(n + max_new, bs) for n in lens)
    per_req = blocks_for_tokens(max(lens) + max_new, bs)
    nb_base = demand + max_slots + 1
    nb_2x = max(demand // 2, per_req + 2) + 1
    nb_4x = max(demand // 4, per_req + 2) + 1

    def build(n_blocks, host_blocks):
        eng = LLMEngine(model, max_slots=max_slots, max_seq_len=S,
                        min_bucket=min_bucket,
                        block_size=bs, n_blocks=n_blocks,
                        prefill_chunk=prefill_chunk,
                        host_kv_blocks=host_blocks)
        # warm one request per power-of-two chunk bucket (+ the decode)
        b, pw = min_bucket, []
        while b <= eng.prefill_chunk:
            pw.append(rng.randint(0, cfg.vocab_size,
                                  size=min(b, S - 3)).tolist())
            b *= 2
        for _ in eng.generate(pw, max_new_tokens=2):
            pass
        if host_blocks:
            # compile the spill/restore programs too: demote the warm
            # chains to the host tier, then touch one so it pages back
            with eng._cond:
                eng._spill_cold(n_blocks)
            for _ in eng.generate([pw[-1]], max_new_tokens=2):
                pass
        eng.prefix.clear()  # measured passes start from a cold tree
        return eng

    def serve(eng, tag):
        before = counters.snapshot()
        t0 = time.perf_counter()
        passes = []
        for _ in range(2):
            hs = [eng.add_request(p, max_new_tokens=max_new)
                  for p in sessions]
            while not all(h.is_finished for h in hs):
                eng.step()
            passes.append(hs)
        wall = time.perf_counter() - t0
        d = counters.delta(before)
        for hs in passes:
            for h in hs:
                if h.finish_reason not in ("length", "eos"):
                    raise AssertionError(
                        f"tiered leg[{tag}]: request finished "
                        f"{h.finish_reason!r} under oversubscription")
            for h, r in zip(hs[:n_verify], refs):
                if not np.array_equal(h.output_ids(), r):
                    raise AssertionError(
                        f"tiered leg[{tag}]: output diverged from "
                        "sequential generate")
        sheds = sum(d.get(k, 0) for k in ("serving.fleet.shed",
                                          "serving.deadline_expired",
                                          "serving.request_errors"))
        tps = 2 * n_sessions * max_new / max(wall, 1e-9)
        return tps, d, sheds

    base = build(nb_base, 0)
    tps_base, _, sheds_base = serve(base, "base")
    del base
    e2x = build(nb_2x, demand)
    tps_2x, d2, sheds_2x = serve(e2x, "2x")
    del e2x
    e4x = build(nb_4x, demand)
    tps_4x, d4, sheds_4x = serve(e4x, "4x")
    del e4x

    # fleet-global prefix economy: the same two-pass traffic through a
    # 2-replica tiered fleet — the router's cost model must keep routing
    # each session's second visit back to the replica holding its prefix
    # (device- or host-resident, restore cost priced in)
    fbefore = counters.snapshot()
    fleet = ServingFleet(model, replicas=2, threaded=False,
                         max_slots=max_slots, max_seq_len=S,
                         min_bucket=min_bucket,
                         block_size=bs, n_blocks=nb_2x,
                         prefill_chunk=prefill_chunk,
                         host_kv_blocks=demand,
                         queue_size=2 * n_sessions + 4)
    for _ in range(2):
        fhs = [fleet.submit(p, max_new_tokens=max_new) for p in sessions]
        fleet.join(fhs)
        for h in fhs:
            if h.finish_reason not in ("length", "eos"):
                raise AssertionError(
                    f"tiered leg[fleet]: request finished "
                    f"{h.finish_reason!r}")
    fleet.drain()
    fd = counters.delta(fbefore)
    del fleet, model

    leg = {"sessions": n_sessions, "passes": 2,
           "max_new_tokens": max_new,
           "block_size": bs,
           "working_set_blocks": demand,
           "kv_blocks_base": nb_base,
           "kv_blocks_2x": nb_2x,
           "kv_blocks_4x": nb_4x,
           "host_kv_blocks": demand,
           "decode_tokens_per_sec_base": round(tps_base, 2),
           "decode_tokens_per_sec_2x": round(tps_2x, 2),
           "decode_tokens_per_sec_4x": round(tps_4x, 2),
           "retention_2x": round(tps_2x / max(tps_base, 1e-9), 4),
           "retention_4x": round(tps_4x / max(tps_base, 1e-9), 4),
           "spilled_blocks": d2.get("serving.kv.tier.spilled_blocks", 0),
           "restored_blocks": d2.get("serving.kv.tier.restored_blocks", 0),
           "readopted": d2.get("serving.kv.tier.readopted", 0),
           "host_buf_reuse": d2.get("serving.kv.host_buf_reuse", 0),
           "spilled_blocks_4x": d4.get("serving.kv.tier.spilled_blocks",
                                       0),
           "sheds": sheds_base + sheds_2x + sheds_4x,
           "steady_retraces_2x": d2.get("serving.retraces", 0),
           "outputs_match_generate": True,
           "fleet": {
               "prefix_routed": fd.get("serving.fleet.prefix_routed", 0),
               "tier_spilled": fd.get("serving.kv.tier.spilled_blocks",
                                      0),
               "tier_restored": fd.get("serving.kv.tier.restored_blocks",
                                       0),
               "sheds": fd.get("serving.fleet.shed", 0),
               "lost": fd.get("serving.fleet.lost", 0)}}
    if leg["sheds"] != 0:
        raise AssertionError(
            f"tiered leg shed/errored requests under oversubscription: "
            f"{leg}")
    if leg["spilled_blocks"] < 1 or leg["restored_blocks"] < 1:
        raise AssertionError(
            f"tiered leg moved no blocks through the host tier at 2x "
            f"oversubscription — the leg is not exercising tiering: "
            f"{leg}")
    if leg["retention_2x"] < min_retention:
        raise AssertionError(
            f"tiered leg decode retention {leg['retention_2x']:.3f}x at "
            f"2x oversubscription below the {min_retention:.2f}x floor: "
            f"{leg}")
    if (leg["fleet"]["lost"] != 0 or leg["fleet"]["sheds"] != 0
            or leg["fleet"]["prefix_routed"] < 1):
        raise AssertionError(
            f"tiered leg fleet pass broke the prefix-economy "
            f"invariants: {leg}")
    return leg


def _parse_mesh_degrees(spec):
    """Parse a ``PTPU_MESH`` string like ``dp2``, ``dp4`` or ``dp2mp2``
    into an ordered ``{axis_name: degree}`` dict."""
    import re

    degrees = {}
    for name, num in re.findall(r"([a-z]+)(\d+)", (spec or "").lower()):
        degrees[name] = int(num)
    return degrees or {"dp": 2}


def _run_servemp_leg(cfg, mp, n_requests=8, max_new=24, max_slots=8,
                     min_bucket=8, block_size=16, prefill_chunk=128,
                     seed=0, max_hbm_frac=0.6, min_tps_frac=0.9):
    """Tensor-parallel paged serving duel: an mp-way mesh engine
    (``LLMEngine(mesh=...)`` — KV pool head-sharded, Megatron-sharded
    weights, replicated operand block tables, in-graph collectives only)
    vs the unsharded engine at EQUAL admitted capacity (same slots, same
    block pool).  Gates: token identity, zero steady retraces on the
    mesh path, per-chip KV-pool + weight HBM bytes <= ``max_hbm_frac``
    of the single-chip figure, and decode tok/s within
    ``1 - min_tps_frac`` of the unsharded baseline.  Returns the leg
    dict."""
    import jax
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.profiler import counters
    from paddle_tpu.serving import LLMEngine

    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(seed)
    S = cfg.max_seq_len
    # decode-heavy mix: short prompts, long generations — the regime
    # tensor parallelism serves (per-token weight sweep dominates)
    lens = [int(rng.randint(max(2, S // 32), max(3, S // 8)))
            for _ in range(n_requests)]
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in lens]

    def build(mesh=None):
        return LLMEngine(model, max_slots=max_slots, max_seq_len=S,
                         min_bucket=min_bucket,
                         block_size=block_size,
                         prefill_chunk=prefill_chunk, mesh=mesh)

    def serve(eng):
        hs = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
        while not all(h.is_finished for h in hs):
            eng.step()
        return [list(map(int, h.tokens)) for h in hs]

    def timed(eng, rounds=3):
        best, toks = 0.0, None
        for _ in range(rounds):
            t0 = time.perf_counter()
            toks = serve(eng)
            tps = (n_requests * max_new
                   / max(time.perf_counter() - t0, 1e-9))
            best = max(best, tps)
        return toks, best

    base = build()
    base_tokens = serve(base)    # warm: full prefills
    serve(base)                  # warm: prefix-cached re-prefills
    _, base_tps = timed(base)
    base_stats = base.stats()
    base_bytes = (base_stats["kv_pool_bytes_per_chip"]
                  + base_stats["weight_bytes_per_chip"])

    mesh = Mesh(np.array(jax.devices()[:mp]).reshape(mp), ("mp",))
    sh = build(mesh)
    sh_tokens = serve(sh)        # warm: full prefills ([mp] programs)
    serve(sh)                    # warm: prefix-cached re-prefills
    before = counters.snapshot()
    sh_tokens2, sh_tps = timed(sh)
    delta = counters.delta(before)
    sh_stats = sh.stats()
    sh_bytes = (sh_stats["kv_pool_bytes_per_chip"]
                + sh_stats["weight_bytes_per_chip"])

    leg = {"mesh": f"mp{mp}",
           "requests": n_requests,
           "max_new_tokens": max_new,
           "decode_tokens_per_sec": round(sh_tps, 2),
           "decode_tokens_per_sec_per_chip": round(sh_tps / mp, 2),
           "unsharded_tokens_per_sec": round(base_tps, 2),
           "tps_frac_vs_unsharded": round(sh_tps / max(base_tps, 1e-9), 4),
           "kv_pool_bytes_per_chip": sh_stats["kv_pool_bytes_per_chip"],
           "weight_bytes_per_chip": sh_stats["weight_bytes_per_chip"],
           "unsharded_kv_pool_bytes": base_stats["kv_pool_bytes_per_chip"],
           "unsharded_weight_bytes": base_stats["weight_bytes_per_chip"],
           "per_chip_hbm_frac": round(sh_bytes / max(base_bytes, 1), 4),
           "outputs_match_unsharded": (sh_tokens == base_tokens
                                       and sh_tokens2 == base_tokens),
           "steady_retraces": delta.get("serving.retraces", 0),
           "spec_degraded": counters.get("serving.mesh.spec_degraded"),
           "kv_shard_shape": list(sh.arena.shard_shape("pool_k"))}
    if not leg["outputs_match_unsharded"]:
        raise AssertionError(
            f"servemp leg: mp{mp} engine diverged from unsharded: {leg}")
    if leg["steady_retraces"]:
        raise AssertionError(
            f"servemp leg: {leg['steady_retraces']} steady retraces on "
            f"the mesh path: {leg}")
    if leg["per_chip_hbm_frac"] > max_hbm_frac:
        raise AssertionError(
            f"servemp leg: per-chip KV+weight bytes "
            f"{leg['per_chip_hbm_frac']:.3f}x of unsharded exceed the "
            f"{max_hbm_frac:.2f}x ceiling: {leg}")
    if leg["tps_frac_vs_unsharded"] < min_tps_frac:
        raise AssertionError(
            f"servemp leg: mesh decode tok/s "
            f"{leg['tps_frac_vs_unsharded']:.3f}x of unsharded below the "
            f"{min_tps_frac:.2f}x floor: {leg}")
    del base, sh, model
    return leg


def _run_mesh_leg(cfg, batch_per_chip, seq, iters, rounds, degrees,
                  peak, fused_steps=1, min_scaling=None):
    """Multi-chip SPMD leg: the same fused training loop run mesh-native
    (``CompiledTrainStep(mesh=...)`` — sharded donated carry, batch staged
    with data-parallel ``NamedSharding``), weak-scaled (constant per-chip
    batch) against a mesh(1) run of the *identical* code path.  Gates the
    steady-state counter contract on the mesh path (zero retraces /
    rehydrates / host binds, dispatches == steps/K) and, when
    ``min_scaling`` is set (real chips only), the dp scaling-efficiency
    floor.  Returns the leg dict."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.io import Window
    from paddle_tpu.jit import CompiledTrainStep
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.profiler import counters
    from paddle_tpu.profiler import metrics as _pm

    k = max(1, int(fused_steps))

    def one(deg):
        # Always carry an "mp" axis (size 1 if unrequested) so any
        # model-declared tensor-parallel placements resolve on the mesh.
        axes = dict(deg)
        if "mp" not in axes:
            axes["mp"] = 1
        ndev = int(np.prod(list(axes.values())))
        if jax.device_count() < ndev:
            raise SystemExit(
                f"mesh leg needs {ndev} devices for {deg}, have "
                f"{jax.device_count()}")
        mesh = Mesh(
            np.array(jax.devices()[:ndev]).reshape(
                tuple(axes.values())),
            tuple(axes.keys()))
        dp = int(np.prod([v for a, v in axes.items()
                          if a in ("dp", "sharding")]))
        batch = batch_per_chip * dp

        paddle.seed(1234)
        model = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion()
        opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
        ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
        labels = paddle.randint(0, cfg.vocab_size, [batch, seq])

        def loss_fn(m, x, l):
            return crit(m(x), l)

        step = CompiledTrainStep(model, loss_fn, opt, fused_steps=k,
                                 mesh=mesh)
        # Stage the batch with its data-parallel sharding up front — the
        # steady loop then re-feeds committed sharded arrays, exercising
        # the same placement the prefetchers produce.
        if step._batch_spec is not None:
            sh = NamedSharding(mesh, step._batch_spec)
            wsh = NamedSharding(mesh, P(None, *step._batch_spec))
            ids = paddle.Tensor(jax.device_put(ids._data, sh))
            labels = paddle.Tensor(jax.device_put(labels._data, sh))
        if k > 1:
            stacked = [np.stack([np.asarray(t.numpy())] * k)
                       for t in (ids, labels)]
            if step._batch_spec is not None:
                stacked = [jax.device_put(s, wsh) for s in stacked]
            win = Window(tuple(paddle.to_tensor(s) for s in stacked), k)
            dispatch = lambda: step(win)
        else:
            dispatch = lambda: step(ids, labels)

        t0 = time.perf_counter()
        dispatch()
        dispatch().numpy()
        compile_s = time.perf_counter() - t0
        dispatch().numpy()  # first fully cached dispatch

        n_windows = max(1, iters // k)
        before = counters.snapshot()
        rates = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(n_windows):
                loss = dispatch()
            loss.numpy()  # sync
            dt = time.perf_counter() - t0
            rates.append(batch * seq * k * n_windows / dt)
        delta = counters.delta(before)
        tps = float(np.median(rates))
        steady = {"retraces": delta.get("jit.traces", 0),
                  "rehydrates": delta.get("jit.hydrates", 0),
                  "host_binds": (delta.get("jit.host.bind_layer_state", 0)
                                 + delta.get(
                                     "jit.host.bind_optimizer_state", 0)),
                  "dispatches": delta.get("jit.host.dispatches", 0),
                  "windows": rounds * n_windows}
        if (steady["retraces"] or steady["rehydrates"]
                or steady["host_binds"]
                or steady["dispatches"] != steady["windows"]):
            raise AssertionError(
                f"mesh leg broke the steady-state counter contract on "
                f"mesh {deg}: {steady}")
        n_params = sum(int(np.prod(p.shape))
                       for p in model.parameters())
        del step, model, opt  # free HBM before the next mesh
        return tps, ndev, n_params, round(compile_s, 4), steady

    base_tps, _, _, base_compile_s, _ = one(
        {a: 1 for a in degrees})
    # device telemetry ON for the mesh pass: per-program HBM bytes (XLA
    # memory analysis at the compile site) land in program_stats; the AOT
    # lower happens at warmup, so the steady-state gate is unaffected
    _flags.set_flags({"FLAGS_device_telemetry": True})
    try:
        tps, ndev, n_params, compile_s, steady = one(degrees)
    finally:
        _flags.set_flags({"FLAGS_device_telemetry": False})
    hbm = {name: {f: st.get(f) for f in
                  ("arg_bytes", "out_bytes", "temp_bytes", "compile_s")}
           for name, st in _pm.program_stats().items()
           if name.startswith("jit.")}
    tps_chip = tps / ndev
    eff = tps_chip / base_tps
    leg = {"mesh": dict(degrees),
           "n_chips": ndev,
           "fused_steps": k,
           "batch_per_chip": batch_per_chip,
           "tokens_per_sec": round(tps, 2),
           "tokens_per_sec_per_chip": round(tps_chip, 2),
           "single_chip_tokens_per_sec": round(base_tps, 2),
           "scaling_efficiency": round(eff, 4),
           "mfu": round(tps_chip * 6 * n_params / peak, 4),
           "compile_s": compile_s,
           "single_chip_compile_s": base_compile_s,
           "steady": steady,
           "hbm": hbm}
    if min_scaling is not None and eff < min_scaling:
        raise AssertionError(
            f"mesh leg scaling efficiency {eff:.3f} below the "
            f"{min_scaling:.2f} floor: {leg}")
    return leg


# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16 (393
# TOP/s is the int8 figure), 16 GB HBM at 819 GB/s.  A device that is not
# in the table is an error, never a default.
_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def main():
    if os.environ.get("PTPU_BENCH_SMOKE") == "1":
        # perf-contract smoke leg: asserts steady-state steps do zero
        # host-side hydrate/bind work (see scripts/bench_smoke.py)
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "scripts"))
        import bench_smoke
        bench_smoke.run()
        return

    import jax

    from paddle_tpu.core import compile_cache
    from paddle_tpu.device import on_tpu
    from paddle_tpu.models import GPTConfig

    dev = jax.devices()[0]
    if not on_tpu():
        raise SystemExit(
            f"bench.py: needs a TPU, found platform {dev.platform!r} — a "
            "speed measured anywhere else is not a result (the CPU suite "
            "is tests/; the chip's first command is chip_smoke.py)")
    if dev.device_kind not in _PEAKS:
        raise SystemExit(
            f"bench.py: no published peak for device_kind "
            f"{dev.device_kind!r}; add it to _PEAKS with its source")
    peak = _PEAKS[dev.device_kind]["bf16_flops"]
    compile_cache.enable()

    fused_k = int(os.environ.get("PTPU_FUSED_STEPS", "4"))

    which = os.environ.get("PTPU_BENCH", "all")
    if which not in ("all", "760m", "125m", "serve", "paged", "paged_q",
                     "tiered", "spec", "ckpt", "fleet", "disagg", "mesh",
                     "mesh760m", "servemp", "multitenant"):
        raise SystemExit(
            f"PTPU_BENCH={which!r}: expected "
            f"all|760m|125m|serve|paged|paged_q|tiered|spec|ckpt|fleet|"
            f"disagg|mesh|mesh760m|servemp|multitenant")
    mesh_degrees = _parse_mesh_degrees(os.environ.get("PTPU_MESH", "dp2"))
    mesh_ndev = int(np.prod(list(mesh_degrees.values())))
    if (which in ("all", "mesh", "mesh760m")
            and jax.device_count() < mesh_ndev):
        # fail before any leg has spent chip time, never skip silently
        raise SystemExit(
            f"bench.py: PTPU_BENCH={which} includes the mesh leg, which "
            f"needs {mesh_ndev} devices for PTPU_MESH="
            f"{os.environ.get('PTPU_MESH', 'dp2')}; found "
            f"{jax.device_count()} — name single-chip legs instead "
            "(PTPU_BENCH=760m|125m|serve|...)")
    legs = {}
    if which in ("all", "760m"):
        cfg = GPTConfig.gpt3_760m(vocab_size=50304, max_seq_len=1024,
                                  dtype="bfloat16",
                                  use_flash_attention=True,
                                  recompute="selective_lean")
        # rounds=4: the first post-compile round can run ~3% cold (seen in
        # r5 combined runs); the median over 4 shakes it off
        tps, spread, n, phases, msum, gput = _run_leg(cfg, 8, 1024, 10, 4)
        legs["gpt760m"] = {"tokens_per_sec": round(tps, 2),
                           "mfu": round(tps * 6 * n / peak, 4),
                           "spread_frac": round(spread, 4),
                           "phases": phases,
                           "metrics": msum,
                           "goodput": gput}
    if which in ("all", "125m"):
        cfg = GPTConfig.gpt3_125m(vocab_size=50304, max_seq_len=1024,
                                  dtype="bfloat16",
                                  use_flash_attention=True,
                                  recompute="selective")
        tps, spread, n, phases, msum, gput = _run_leg(cfg, 16, 1024, 15, 3)
        legs["gpt125m"] = {"tokens_per_sec": round(tps, 2),
                           "mfu": round(tps * 6 * n / peak, 4),
                           "spread_frac": round(spread, 4),
                           "phases": phases,
                           "metrics": msum,
                           "goodput": gput}
        if fused_k > 1:
            # fused-dispatch leg: same model/config, K steps per XLA
            # launch — isolates the per-step python dispatch overhead
            # that the 125m leg is most exposed to
            ftps, fspread, n, fphases, fmsum, fgput = _run_leg(
                cfg, 16, 1024, 16, 3, fused_steps=fused_k)
            legs["gpt125m_fused"] = {
                "fused_steps": fused_k,
                "tokens_per_sec": round(ftps, 2),
                "mfu": round(ftps * 6 * n / peak, 4),
                "fused_speedup": round(ftps / tps, 4),
                "spread_frac": round(fspread, 4),
                "phases": fphases,
                "metrics": fmsum,
                "goodput": fgput}
    if which in ("all", "ckpt"):
        # checkpointed-training leg: steady fused windows with async saves
        # overlapping the next window — reports ckpt_overhead_frac and
        # gates the one-sync-per-save counter budget
        ccfg = GPTConfig.gpt3_125m(vocab_size=50304, max_seq_len=1024,
                                   dtype="bfloat16",
                                   use_flash_attention=True,
                                   recompute="selective")
        legs["gpt125m_ckpt"] = _run_ckpt_leg(ccfg, 16, 1024, 16,
                                             fused_steps=max(1, fused_k))
    if which in ("all", "serve"):
        # serving leg: continuous batching over 64 staggered mixed-length
        # requests with TTFT/ITL/queue-wait percentiles (acceptance:
        # serve_speedup > 1 on TPU, verified prefix token-identical to
        # sequential generate always)
        scfg = GPTConfig.gpt3_125m(vocab_size=50304, max_seq_len=1024,
                                   dtype="bfloat16",
                                   use_flash_attention=False,
                                   recompute=None)
        legs["gpt125m_serve"] = _run_serve_leg(scfg, n_requests=64,
                                               max_new=64, max_slots=8)
    if which in ("all", "paged"):
        # paged-KV leg: >=2x max_slots admitted in the KV HBM of max_slots
        # sequences of S_max on mixed lengths, plus shared-system-prompt TTFT tails and
        # the prefix-cache hit / reduced-prefill gates
        pcfg = GPTConfig.gpt3_125m(vocab_size=50304, max_seq_len=1024,
                                   dtype="bfloat16",
                                   use_flash_attention=False,
                                   recompute=None)
        legs["gpt125m_paged"] = _run_paged_leg(pcfg, n_requests=64,
                                               max_new=64, max_slots=8,
                                               block_size=16,
                                               prefill_chunk=256)
    if which in ("all", "paged_q"):
        # quantized-KV leg: int8 arena vs bf16 paged at the same KV HBM
        # byte budget — >=2x admitted concurrency, decode tok/s no worse
        qcfg = GPTConfig.gpt3_125m(vocab_size=50304, max_seq_len=1024,
                                   dtype="bfloat16",
                                   use_flash_attention=False,
                                   recompute=None)
        legs["gpt125m_paged_q"] = _run_paged_q_leg(qcfg, n_requests=64,
                                                   max_new=64, max_slots=4,
                                                   block_size=16,
                                                   prefill_chunk=256)
    if which in ("all", "tiered"):
        # KV-tiering leg: device pool cut to 1/2 and 1/4 of the working
        # set with a pinned host tier covering the difference — gates
        # token identity, zero sheds, live spill/restore traffic and
        # >=0.5x decode retention at 2x oversubscription, plus the
        # fleet router's host-aware prefix-affinity wins
        tcfg = GPTConfig.gpt3_125m(vocab_size=50304, max_seq_len=1024,
                                   dtype="bfloat16",
                                   use_flash_attention=False,
                                   recompute=None)
        legs["gpt125m_tiered"] = _run_tiered_leg(tcfg, n_sessions=24,
                                                 max_new=64, max_slots=8,
                                                 block_size=16,
                                                 prefill_chunk=256)
    if which in ("all", "spec"):
        # speculative-decoding leg: aligned draft/target pair (shared
        # embeddings, zeroed blocks -> acceptance ~1.0) at gpt125m width
        # and depth — acceptance rate, net decode tok/s vs the non-spec
        # paged baseline (>= 1.3x), TTFT/ITL tails, zero steady retraces
        legs["gpt125m_spec"] = _run_spec_leg(n_requests=32, max_new=64,
                                             max_slots=8, hidden=768,
                                             layers=12, vocab=50304,
                                             seq_len=1024, block_size=16,
                                             prefill_chunk=256)
    if which in ("all", "fleet"):
        # elastic-fleet leg: multi-replica throughput with and without
        # one replica killed mid-decode (acceptance: zero lost requests,
        # churn output token-identical to the clean run)
        fcfg = GPTConfig.gpt3_125m(vocab_size=50304, max_seq_len=1024,
                                   dtype="bfloat16",
                                   use_flash_attention=False,
                                   recompute=None)
        legs["gpt125m_fleet"] = _run_fleet_leg(fcfg, replicas=2,
                                               n_requests=8, max_new=64,
                                               max_slots=4)
    if which in ("all", "multitenant"):
        # multi-tenant adapter leg: 6 LoRA tenants through a 2-replica
        # fleet whose per-replica arena holds 4 — cold tenants page in,
        # LRU evicts idle (acceptance: fair pass token-identical across
        # repeats with zero steady retraces, zero lost, the injected
        # adapter_load_drop recovering to a finished request, and
        # loads/evictions both moving — paging genuinely exercised)
        mtcfg = GPTConfig.gpt3_125m(vocab_size=50304, max_seq_len=1024,
                                    dtype="bfloat16",
                                    use_flash_attention=False,
                                    recompute=None)
        legs["gpt125m_multitenant"] = _run_multitenant_leg(
            mtcfg, replicas=2, tenants=6, adapter_slots=4, rank=8,
            n_requests=12, max_new=64, max_slots=4, block_size=16,
            prefill_chunk=256)
    if which in ("all", "disagg"):
        # disaggregated prefill/decode leg: 1+1 split vs 2-replica
        # unified on mixed long/short traffic (acceptance: >=1.3x p95
        # ITL win at equal replica count, zero lost under migration
        # chaos, token identity to the unified fleet)
        dcfg = GPTConfig.gpt3_125m(vocab_size=50304, max_seq_len=1024,
                                   dtype="bfloat16",
                                   use_flash_attention=False,
                                   recompute=None)
        legs["gpt125m_disagg"] = _run_disagg_leg(dcfg, n_long=6,
                                                 n_short=18, max_new=64,
                                                 block_size=16,
                                                 prefill_chunk=256)
    if which in ("all", "mesh"):
        # multi-chip SPMD leg: weak-scaled fused training on the
        # PTPU_MESH mesh vs a mesh(1) run of the same code path
        # (acceptance: zero steady retraces, dispatches == steps/K,
        # >=70% dp scaling efficiency)
        mcfg = GPTConfig.gpt3_125m(vocab_size=50304, max_seq_len=1024,
                                   dtype="bfloat16",
                                   use_flash_attention=True,
                                   recompute="selective")
        legs["gpt125m_mesh"] = _run_mesh_leg(mcfg, 16, 1024, 16, 3,
                                             mesh_degrees,
                                             fused_steps=max(1, fused_k),
                                             peak=peak, min_scaling=0.70)
    if which == "servemp":
        # tensor-parallel serving leg: mp-way mesh paged engine vs the
        # unsharded engine at EQUAL admitted capacity (acceptance: token
        # identity, zero steady retraces, per-chip KV+weight HBM <= 0.6x
        # single-chip, decode tok/s >= 0.9x unsharded). Runs the 760m
        # flagship.
        mp = _parse_mesh_degrees(os.environ.get("PTPU_MESH", "mp2")
                                 ).get("mp", 2)
        vcfg = GPTConfig.gpt3_760m(vocab_size=50304, max_seq_len=1024,
                                   dtype="bfloat16",
                                   use_flash_attention=False,
                                   recompute=None)
        legs["gpt760m_servemp"] = _run_servemp_leg(
            vcfg, mp, n_requests=16, max_new=64, max_slots=8,
            block_size=16, prefill_chunk=256)
    if which == "mesh760m":
        mcfg = GPTConfig.gpt3_760m(vocab_size=50304, max_seq_len=1024,
                                   dtype="bfloat16",
                                   use_flash_attention=True,
                                   recompute="selective_lean")
        legs["gpt760m_mesh"] = _run_mesh_leg(mcfg, 8, 1024, 8, 3,
                                             mesh_degrees,
                                             fused_steps=max(1, fused_k),
                                             peak=peak, min_scaling=0.70)

    if set(legs) in ({"gpt125m_mesh"}, {"gpt760m_mesh"}):
        # mesh-only run: per-chip throughput line, MFU as vs_baseline
        name, = legs
        leg = legs[name]
        print(json.dumps({
            "metric": f"{name}_train_tokens_per_sec_per_chip",
            "value": leg["tokens_per_sec_per_chip"],
            "unit": "tokens/s/chip",
            "vs_baseline": leg["mfu"],  # true MFU fraction (bf16 peak)
            "scaling_efficiency": leg["scaling_efficiency"],
            "legs": legs,
        }))
        return
    if set(legs) == {"gpt760m_servemp"}:  # servemp-only: per-chip line
        leg = legs["gpt760m_servemp"]
        print(json.dumps({
            "metric": "gpt760m_servemp_decode_tokens_per_sec_per_chip",
            "value": leg["decode_tokens_per_sec_per_chip"],
            "unit": "tokens/s/chip",
            "vs_baseline": leg["per_chip_hbm_frac"],  # KV+W vs 1 chip
            "tps_frac_vs_unsharded": leg["tps_frac_vs_unsharded"],
            "legs": legs,
        }))
        return
    if set(legs) == {"gpt125m_fleet"}:  # fleet-only run: durability line
        leg = legs["gpt125m_fleet"]
        print(json.dumps({
            "metric": "gpt125m_fleet_decode_tokens_per_sec",
            "value": leg["decode_tokens_per_sec"],
            "unit": "tokens/s",
            "vs_baseline": leg["churn_retention"],  # vs one replica killed
            "legs": legs,
        }))
        return
    if set(legs) == {"gpt125m_multitenant"}:  # adapters-only: tenant line
        leg = legs["gpt125m_multitenant"]
        print(json.dumps({
            "metric": "gpt125m_multitenant_decode_tokens_per_sec",
            "value": leg["decode_tokens_per_sec"],
            "unit": "tokens/s (6 tenants + base, one decode program)",
            "vs_baseline": leg["tenants_per_slot"],  # variants per slot
            "noisy_itl_p95_skew": leg["noisy_itl_p95_skew"],
            "legs": legs,
        }))
        return
    if set(legs) == {"gpt125m_disagg"}:  # disagg-only: ITL-win line
        leg = legs["gpt125m_disagg"]
        print(json.dumps({
            "metric": "gpt125m_disagg_itl_p95_speedup",
            "value": leg["itl_p95_speedup"],
            "unit": "x unified p95 ITL at equal replica count",
            "vs_baseline": leg["disagg_itl"]["p95_ms"],
            "legs": legs,
        }))
        return
    if set(legs) == {"gpt125m_spec"}:  # spec-only run: speedup line
        leg = legs["gpt125m_spec"]
        print(json.dumps({
            "metric": "gpt125m_spec_decode_tokens_per_sec",
            "value": leg["decode_tokens_per_sec_spec"],
            "unit": "tokens/s",
            "vs_baseline": leg["spec_speedup"],  # vs non-spec paged
            "acceptance_rate": leg["acceptance_rate"],
            "legs": legs,
        }))
        return
    if set(legs) == {"gpt125m_tiered"}:  # tiered-only: retention line
        leg = legs["gpt125m_tiered"]
        print(json.dumps({
            "metric": "gpt125m_tiered_decode_tokens_per_sec_2x",
            "value": leg["decode_tokens_per_sec_2x"],
            "unit": "tokens/s at 2x oversubscribed KV",
            "vs_baseline": leg["retention_2x"],  # vs ample-pool paged
            "retention_4x": leg["retention_4x"],
            "legs": legs,
        }))
        return
    if set(legs) == {"gpt125m_paged_q"}:  # paged_q-only: quant capacity
        leg = legs["gpt125m_paged_q"]
        print(json.dumps({
            "metric": "gpt125m_paged_q_admitted_capacity_ratio",
            "value": leg["capacity_ratio"],
            "unit": "x admitted vs bf16 paged at fixed KV HBM",
            "vs_baseline": leg["decode_parity"],  # quant vs raw tok/s
            "legs": legs,
        }))
        return
    if set(legs) == {"gpt125m_paged"}:  # paged-only run: capacity line
        leg = legs["gpt125m_paged"]
        print(json.dumps({
            "metric": "gpt125m_paged_decode_tokens_per_sec",
            "value": leg["decode_tokens_per_sec_paged"],
            "unit": "tokens/s",
            "vs_baseline": leg["capacity_ratio"],  # peak admits vs rows
            "legs": legs,
        }))
        return
    if set(legs) == {"gpt125m_ckpt"}:  # ckpt-only run: overhead line
        leg = legs["gpt125m_ckpt"]
        print(json.dumps({
            "metric": "gpt125m_ckpt_tokens_per_sec",
            "value": leg["tokens_per_sec"],
            "unit": "tokens/s",
            "vs_baseline": leg["ckpt_overhead_frac"],  # vs bare loop
            "legs": legs,
        }))
        return
    flag = ("gpt760m" if "gpt760m" in legs
            else "gpt125m" if "gpt125m" in legs else "gpt125m_serve")
    if flag == "gpt125m_serve":  # serve-only run: decode throughput line
        leg = legs[flag]
        print(json.dumps({
            "metric": "gpt125m_serve_decode_tokens_per_sec",
            "value": leg["decode_tokens_per_sec"],
            "unit": "tokens/s",
            "vs_baseline": leg["serve_speedup"],  # vs sequential generate
            "legs": legs,
        }))
        return
    print(json.dumps({
        "metric": f"{flag}_train_tokens_per_sec_per_chip",
        "value": legs[flag]["tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": legs[flag]["mfu"],  # true MFU fraction (bf16 peak)
        "spread_frac": legs[flag]["spread_frac"],
        "legs": legs,
    }))


if __name__ == "__main__":
    main()
