"""Counter-verified steady-state gate: a short CompiledTrainStep run must
reach a zero-python-overhead steady state, proven by the process-global
``paddle_tpu.profiler.counters`` registry rather than by timing.

Protocol: 2 warmup steps (step 1 hydrates + traces, step 2 retraces once —
the optimizer accumulators change the carried-state structure), then 2
measured steps which must show:

  * 0 retraces           (jit.traces — the python step body never re-runs)
  * 0 rehydrations       (jit.hydrates)
  * 0 host bind/sync work (jit.host.*, jit.syncs)
  * 2 cache hits, 0 misses (every dispatch is a pure jit-cache hit)

A second phase gates the fused multi-step dispatch path
(``fused_steps=K``): after its warmup (window 1 = priming single-step
fallback, window 2 = scan compile), every measured K-step window must be
exactly ONE XLA dispatch — ``jit.host.dispatches == jit.steps / K`` —
again with zero retraces / rehydrates / host binds.

A third phase gates the serving engine (``paddle_tpu.serving.LLMEngine``):
warmup requests compile one prefill chunk program per power-of-two
bucket plus the single decode program and the COW copy; measured
requests that reuse those buckets must show ``serving.retraces == 0``
and zero jit.* trace/hydrate/host-bind movement — continuous batching
reaches the same zero-python-overhead steady state as training.

A fourth phase gates the elastic serving fleet
(``paddle_tpu.serving.ServingFleet``): the no-fault fleet must be
token-identical to the single engine with zero steady-state retraces
(``warm_buckets`` pre-compiles every replica), and a churn run under a
deterministic ``replica_crash`` schedule must show
``serving.fleet.lost == 0`` with ``respawns``/``retried`` equal to the
injected fault count — zero lost requests under churn.

A fifth phase gates checkpointed training (``paddle_tpu.resilience``):
a warm step interleaved with ``CheckpointManager.save`` calls must show
zero retraces/rehydrates and zero host sync work beyond the ONE
counter-gated ``sync()`` per save (``jit.syncs == saves``, with exactly
one ``bind_layer_state``/``bind_optimizer_state`` pair each and zero
``layer_state``/``optimizer_state`` re-reads); then a
``FaultTolerantTrainer`` run under a deterministic fault schedule must
show ``resilience.restores == injected preemptions``.

A sixth phase gates the multi-chip SPMD mesh path
(``CompiledTrainStep(mesh=...)``): on >=4 devices (forced host devices in
CI) a 2x2 dp/mp mesh with a ``shard_rules`` tensor-parallel split must
prove its weights actually live sharded (local shard shape check), reach
the SAME steady-state economics as the single-device path — zero
retraces / rehydrates / host binds, ``dispatches == MEASURE``, and
``dist.collective_launches == 0`` (GSPMD collectives are compiled into
the program, never host-issued) — and the fused-on-mesh run must keep
``dispatches == steps/K``.

A seventh phase gates the telemetry subsystem's zero-overhead claim
(``profiler.metrics``): every steady-state phase above (train, fused,
mesh dp2, serving) is run twice with fresh objects — metrics OFF, then
metrics ON (``CompiledTrainStep(metrics=True)``; telemetry harvested
inside the measured window) — and the ``jit.syncs`` / ``jit.traces`` /
``jit.host.dispatches`` / ``serving.retraces`` deltas must be IDENTICAL:
in-graph metric accumulation and host-side harvesting add zero syncs,
zero retraces, zero extra dispatches.

An eighth phase gates request tracing (``profiler.trace``) the same two
ways: with ``FLAGS_request_trace_sample=0`` a fresh engine + fleet
workload must move ZERO ``trace.*`` counters and must be
counter-identical (same parity keys: zero extra retraces / hydrates /
host dispatches / syncs) to the tracing-ON run of the identical
workload; with sample=1, every finished engine request's stage spans
(queue + prefill + decode) must sum within tolerance of its measured
TTFT + decode wall clock — the span tree accounts for the latency the
histograms report.

A ninth phase gates speculative decoding
(``LLMEngine(draft_model=...)``): greedy speculative output must be
token-identical to the non-speculative paged engine, a warm measured
window must dispatch only cached programs (zero retraces / traces /
hydrates / syncs) while the engine's whole lifetime compiled exactly
ONE draft decode + ONE verify program, and the acceptance ledger must
balance exactly — ``serving.spec.accepted + rejected == drafted`` with
K+1 draft launches + ONE verify launch per round.  The program-audit
phase additionally serves through a speculative engine under
``FLAGS_program_audit=enforce`` with OFF/ON counter parity.

A tenth phase gates the disaggregated prefill/decode split
(``ServingFleet(prefill_replicas=...)``): a 1+1 split must be
token-identical to the unified paged fleet on the same prompts, every
hand-off must copy EXACTLY the owned non-shared KV blocks
(``serving.fleet.migrate.blocks_copied`` equals the block-table size
minus the block-aligned prefix resolved against the decode replica's
radix tree — a shared prefix is never moved twice), and the measured
hand-offs must retrace nothing once the warm pass has compiled the
migration gather.

An eleventh phase gates the host-RAM KV tier
(``LLMEngine(host_kv_blocks=...)``): a paged engine whose block pool is
far smaller than its working set must stay token-identical — greedy AND
seeded sampling — to the ample-pool engine while cold prefix chains
spill to pinned host buffers and page back on demand; the measured
spill/restore churn must retrace/trace/sync NOTHING and must not grow
the host arena (every buffer comes from the reuse pool:
``serving.kv.host_buf_reuse`` moves, ``serving.kv.host_arena_bytes``
does not); and a ``kv_spill_drop`` fault mid-restore must degrade to a
deterministic cache-miss replay with identical tokens and a reconciled
block pool.

A twelfth phase gates the device-time ledger
(``profiler.devicetime``): with ``FLAGS_device_time_sample=0`` a fresh
plain + speculative workload must move ZERO ``jit.devicetime.*``
/ ``program.*`` state and be counter-identical on the parity keys to
the sampling-ON run of the identical workload; with sample=4 the
measured window must pay EXACTLY ``ceil(dispatches / 4)`` sampled
block-until-ready fences (``jit.devicetime.sampled_syncs``) with token
identity and zero retraces, and the ledger it leaves behind must carry
MFU/roofline gauges that survive ``GET /programs`` and a
``bench_compare.py --attribute`` run that names the dominant program.

A thirteenth phase gates tensor-parallel serving over the StateArena
(``serving.arena``): an mp2 paged engine must be token-identical to the
single-device engine (greedy AND seeded) with the zero-steady-retrace
economics and dispatch counts unchanged, the KV pool genuinely
head-sharded per chip, and every cross-chip reduction an in-graph
collective under the auditor's compiled-HLO census.

A fourteenth phase gates multi-tenant LoRA serving
(``serving.adapters``): ONE compiled decode program serves any tenant
mix — a heterogeneous batch (three tenants + a base row in the same
decode step) must be token-identical to running each tenant
sequentially, base-only traffic through an adapter engine must match
the adapter-free twin row for row, the warm steady window must move
ZERO retraces/hydrates/syncs/arena-misses with dispatch counts equal to
the adapter-free reference, and an eviction-then-reuse cycle (more
tenants than arena slots) must page the evicted tenant back in warm —
``serving.adapter.loads`` moves, programs never retrace, tokens never
change.

Prints one JSON line; raises AssertionError on any violation.  Wired as a
tier-1 test via tests/test_profiler.py.  Run directly:
``python scripts/check_counters.py``.
"""

import json
import os
import time

WARMUP = 2
MEASURE = 2
FUSED_K = 2
FUSED_MEASURE = 2  # measured windows = FUSED_MEASURE * FUSED_K steps
SERVE_LENS_WARM = (3, 6)      # buckets {4, 8} with min_bucket=4
SERVE_LENS_MEASURE = (4, 5)   # same buckets — must retrace NOTHING


def run():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the mesh gate needs >1 device; only effective before the first jax
    # import (tests/conftest.py sets the same flag), no-op on real TPUs
    if ("--xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    import paddle_tpu as paddle
    import paddle_tpu.jit as pjit
    import paddle_tpu.nn as nn
    from paddle_tpu.profiler import counters

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    x = paddle.randn([8, 16])
    y = paddle.randn([8, 4])

    def loss_fn(m, a, b):
        return ((m(a) - b) ** 2).mean()

    step = pjit.CompiledTrainStep(model, loss_fn, opt)
    for _ in range(WARMUP):
        step(x, y).numpy()
    before = counters.snapshot()
    for _ in range(MEASURE):
        step(x, y).numpy()
    steady = counters.delta(before)

    invariants = {
        "jit.traces": 0,
        "jit.hydrates": 0,
        "jit.syncs": 0,
        "jit.cache_misses": 0,
        "jit.cache_hits": MEASURE,
        "jit.steps": MEASURE,
        "jit.host.dispatches": MEASURE,  # single-step mode: 1 launch/step
    }
    invariants.update({"jit.host." + k: 0 for k in pjit._HOST_SYNC_KEYS})

    violations = {k: (steady.get(k, 0), want)
                  for k, want in invariants.items()
                  if steady.get(k, 0) != want}

    # ---- fused multi-step dispatch gate: dispatches == steps / K --------
    from paddle_tpu.io import Window

    paddle.seed(0)
    fmodel = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
    fopt = paddle.optimizer.AdamW(1e-3, parameters=fmodel.parameters())
    fstep = pjit.CompiledTrainStep(fmodel, loss_fn, fopt,
                                   fused_steps=FUSED_K)
    import numpy as np
    rng = np.random.RandomState(0)
    def window():
        return Window(
            (paddle.to_tensor(rng.randn(FUSED_K, 8, 16).astype("float32")),
             paddle.to_tensor(rng.randn(FUSED_K, 8, 4).astype("float32"))),
            FUSED_K)
    fstep(window()).numpy()  # window 1: priming single-step fallback
    fstep(window()).numpy()  # window 2: scan compile
    fbefore = counters.snapshot()
    for _ in range(FUSED_MEASURE):
        fstep(window()).numpy()
    fsteady = counters.delta(fbefore)

    finvariants = {
        "jit.traces": 0,
        "jit.hydrates": 0,
        "jit.syncs": 0,
        "jit.cache_misses": 0,
        "jit.cache_hits": FUSED_MEASURE,
        "jit.steps": FUSED_MEASURE * FUSED_K,
        "jit.fused_windows": FUSED_MEASURE,
        "jit.fused_fallback_steps": 0,
        # THE fused-dispatch economics gate: one launch per K-step window
        "jit.host.dispatches": (FUSED_MEASURE * FUSED_K) // FUSED_K,
    }
    finvariants.update({"jit.host." + k: 0 for k in pjit._HOST_SYNC_KEYS})
    violations.update({f"fused:{k}": (fsteady.get(k, 0), want)
                       for k, want in finvariants.items()
                       if fsteady.get(k, 0) != want})

    # ---- mesh gate: the multi-chip SPMD path keeps the same economics ---
    import jax
    if jax.device_count() >= 4:
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("dp", "mp"))
        paddle.seed(0)
        mmodel = nn.Sequential(nn.Linear(16, 32), nn.GELU(),
                               nn.Linear(32, 4))
        mopt = paddle.optimizer.AdamW(1e-3,
                                      parameters=mmodel.parameters())
        mstep = pjit.CompiledTrainStep(
            mmodel, loss_fn, mopt, mesh=mesh,
            shard_rules=[(r"\.weight$", P(None, "mp"))])
        for _ in range(WARMUP):
            mstep(x, y).numpy()
        # sharded-placement proof: the (16, 32) Linear weight split over
        # mp=2 must live as (16, 16) local shards, not a replicated copy.
        # The live weights sit in the donated carry (mstep._state), not in
        # the model's stale host-bound params.
        w = next(v for v in jax.tree_util.tree_leaves(mstep._state[0])
                 if tuple(v.shape) == (16, 32))
        shard_shape = tuple(w.addressable_shards[0].data.shape)
        if shard_shape != (16, 16):
            violations["mesh:weight_shard_shape"] = (shard_shape,
                                                     (16, 16))
        mbefore = counters.snapshot()
        for _ in range(MEASURE):
            mstep(x, y).numpy()
        msteady = counters.delta(mbefore)
        minvariants = dict(invariants)
        # GSPMD collectives are compiled into the step program — the
        # steady state must issue ZERO host-side collective launches
        minvariants["dist.collective_launches"] = 0
        violations.update({f"mesh:{k}": (msteady.get(k, 0), want)
                           for k, want in minvariants.items()
                           if msteady.get(k, 0) != want})

        # fused-on-mesh: one XLA launch per K-step window, same as the
        # single-device fused gate
        paddle.seed(0)
        fmmodel = nn.Sequential(nn.Linear(16, 32), nn.GELU(),
                                nn.Linear(32, 4))
        fmopt = paddle.optimizer.AdamW(1e-3,
                                       parameters=fmmodel.parameters())
        fmstep = pjit.CompiledTrainStep(
            fmmodel, loss_fn, fmopt, fused_steps=FUSED_K, mesh=mesh,
            shard_rules=[(r"\.weight$", P(None, "mp"))])
        fmstep(window()).numpy()  # priming single-step fallback
        fmstep(window()).numpy()  # scan compile
        fmbefore = counters.snapshot()
        for _ in range(FUSED_MEASURE):
            fmstep(window()).numpy()
        fmsteady = counters.delta(fmbefore)
        fminvariants = dict(finvariants)
        fminvariants["dist.collective_launches"] = 0
        violations.update({f"mesh-fused:{k}": (fmsteady.get(k, 0), want)
                           for k, want in fminvariants.items()
                           if fmsteady.get(k, 0) != want})
    else:
        msteady = {"skipped":
                   f"needs 4 devices, have {jax.device_count()}"}
        fmsteady = msteady

    # ---- serving steady-state gate: warm buckets never retrace ----------
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import LLMEngine

    paddle.seed(0)
    scfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=4, max_seq_len=32,
                     use_flash_attention=False)
    smodel = GPTForCausalLM(scfg)
    smodel.eval()
    rng = np.random.RandomState(7)
    # block tables are int32 OPERANDS, so the warm chunk buckets + ONE
    # decode program + ONE COW copy program must cover the measure window
    # with zero retraces/hydrates/host binds.
    peng = LLMEngine(smodel, max_slots=2, max_seq_len=32, min_bucket=4,
                     block_size=4, prefill_chunk=8)

    def pserve(eng_, lens):
        hs = [eng_.add_request(rng.randint(0, 64, size=n).tolist(),
                               max_new_tokens=3) for n in lens]
        while not all(h.is_finished for h in hs):
            eng_.step()
        return hs

    ph0 = pserve(peng, SERVE_LENS_WARM)[0]
    # warm the copy-on-write program too: extend a sequence the warm
    # requests left in the prefix tree past its cached partial block
    cow_warm = (list(ph0.prompt) + ph0.tokens)[:5] + [int(ph0.prompt[0])]
    pserve_cow = peng.add_request(cow_warm, max_new_tokens=3)
    while not pserve_cow.is_finished:
        peng.step()

    pbefore = counters.snapshot()
    phs = pserve(peng, SERVE_LENS_MEASURE)
    psteady = counters.delta(pbefore)
    pinvariants = {
        "serving.retraces": 0,
        "jit.traces": 0,
        "jit.hydrates": 0,
        "jit.syncs": 0,
        "serving.requests": len(SERVE_LENS_MEASURE),
        "serving.evictions": len(SERVE_LENS_MEASURE),
    }
    pinvariants.update({"jit.host." + k: 0 for k in pjit._HOST_SYNC_KEYS})
    violations.update({f"paged:{k}": (psteady.get(k, 0), want)
                       for k, want in pinvariants.items()
                       if psteady.get(k, 0) != want})
    # a launch on which no slot changed hands is enqueued before the one
    # in flight is read back
    if not psteady.get("serving.decode.overlapped_steps", 0) > 0:
        violations["paged:serving.decode.overlapped_steps"] = (
            psteady.get("serving.decode.overlapped_steps", 0), ">0")
    for h in phs:   # paged output must equal sequential generate
        pref = np.asarray(smodel.generate(
            paddle.to_tensor(np.asarray([list(h.prompt)])),
            max_new_tokens=3).numpy())[0][len(h.prompt):].tolist()
        if h.tokens != pref:
            violations[f"paged:identity@{h.rid}"] = (h.tokens, pref)

    # shared-prefix leg: against a no-cache twin serving the SAME
    # workload, the prefix cache must score hits and launch strictly
    # fewer prefill chunks
    psys = rng.randint(0, 64, size=12).tolist()
    ptails = [rng.randint(0, 64, size=4).tolist() for _ in range(3)]
    pnc = LLMEngine(smodel, max_slots=2, max_seq_len=32, min_bucket=4,
                    block_size=4, prefill_chunk=8,
                    prefix_cache=False)
    ncbefore = counters.snapshot()
    for t in ptails:
        h = pnc.add_request(psys + t, max_new_tokens=3)
        while not h.is_finished:
            pnc.step()
    nc_chunks = counters.delta(ncbefore).get("serving.kv.prefill_chunks", 0)
    pc = LLMEngine(smodel, max_slots=2, max_seq_len=32, min_bucket=4,
                   block_size=4, prefill_chunk=8)
    pcbefore = counters.snapshot()
    for t in ptails:    # sequential, so each finish feeds the tree
        h = pc.add_request(psys + t, max_new_tokens=3)
        while not h.is_finished:
            pc.step()
    pcdelta = counters.delta(pcbefore)
    pc_chunks = pcdelta.get("serving.kv.prefill_chunks", 0)
    pc_hits = pcdelta.get("serving.kv.prefix_hits", 0)
    if pc_hits < 2:
        violations["paged-prefix:hits"] = (pc_hits, ">=2")
    if not pc_chunks < nc_chunks:
        violations["paged-prefix:chunks"] = (pc_chunks, f"<{nc_chunks}")

    # ---- paged Pallas-kernel + quantized-KV gate ------------------------
    # The fused Pallas decode kernel (interpret mode on CPU) and the int8
    # arena must be drop-in twins of the plain-XLA paged engine: pallas is
    # TOKEN-identical (greedy and seeded sampling), quantized KV / PTQ
    # weights hold the documented logit-tolerance gate
    # (max |drift| <= 5% of the fp32 logit magnitude), and both keep the
    # steady-state economics — distinct program-cache keys, ONE decode
    # program per backend (kernels.paged.* tick once, at trace time), and
    # zero retraces in a warm measure window.
    import jax.numpy as jnp
    from paddle_tpu.kernels import paged_attention as _pa
    from paddle_tpu.quantization import ptq_int8_decode_state

    pq_prompts = [rng.randint(0, 64, size=n).tolist() for n in (5, 9)]
    pq_sample = dict(do_sample=True, temperature=0.9, top_k=8)

    def pq_engine(**kw):
        return LLMEngine(smodel, max_slots=2, max_seq_len=32, min_bucket=4,
                         block_size=4, prefill_chunk=8,
                         **kw)

    def pq_run(eng_, sampled=False):
        hs = [eng_.add_request(p, max_new_tokens=3, seed=21 + i,
                               **(pq_sample if sampled else {}))
              for i, p in enumerate(pq_prompts)]
        while not all(h.is_finished for h in hs):
            eng_.step()
        return [list(h.tokens) for h in hs]

    pq_base = pq_engine()
    base_greedy = pq_run(pq_base)
    base_sampled = pq_run(pq_base, sampled=True)

    _pa._INTERPRET[0] = True       # the hook: the kernel runs anywhere
    try:
        kbefore = counters.snapshot()
        pk_eng = pq_engine()
        if pk_eng.stats()["kv_kernel"] != "pallas":
            violations["paged-pallas:kv_kernel"] = (
                pk_eng.stats()["kv_kernel"], "pallas")
        pk_greedy = pq_run(pk_eng)              # traces the pallas decode
        pk_sampled = pq_run(pk_eng, sampled=True)
        kwarm = counters.delta(kbefore)
        # the fused backend actually compiled, and never fell back
        if kwarm.get("kernels.paged.pallas_programs", 0) < 1:
            violations["paged-pallas:programs"] = (
                kwarm.get("kernels.paged.pallas_programs", 0), ">=1")
        if kwarm.get("kernels.paged.xla_fallbacks", 0):
            violations["paged-pallas:fallbacks"] = (
                kwarm.get("kernels.paged.xla_fallbacks", 0), 0)
        if pk_greedy != base_greedy:
            violations["paged-pallas:greedy_identity"] = (pk_greedy,
                                                          base_greedy)
        if pk_sampled != base_sampled:
            violations["paged-pallas:sampled_identity"] = (pk_sampled,
                                                           base_sampled)
        # warm steady window: every program (incl. the kernel) cached
        ksbefore = counters.snapshot()
        pq_run(pk_eng)
        ksteady = counters.delta(ksbefore)
        for k in ("serving.retraces", "jit.traces", "jit.hydrates",
                  "jit.syncs", "kernels.paged.pallas_programs",
                  "kernels.paged.xla_fallbacks"):
            if ksteady.get(k, 0):
                violations[f"paged-pallas:{k}"] = (ksteady.get(k, 0), 0)
    finally:
        _pa._INTERPRET[0] = False

    # int8 arena twin: greedy-identical on the tiny model, ONE decode
    # program for the whole engine lifetime, zero steady retraces
    qbefore = counters.snapshot()
    pq_q = pq_engine(kv_dtype="int8")
    q_greedy = pq_run(pq_q)
    qwarm = counters.delta(qbefore)
    if q_greedy != base_greedy:
        violations["paged-quant:greedy_identity"] = (q_greedy, base_greedy)
    if qwarm.get("kernels.paged.xla_fallbacks", 0) != 1:
        violations["paged-quant:decode_programs"] = (
            qwarm.get("kernels.paged.xla_fallbacks", 0), 1)
    if not qwarm.get("serving.kv.quant.prefill_tokens", 0):
        violations["paged-quant:prefill_tokens"] = (0, ">0")
    if counters.get("serving.kv.quant.bytes_saved") <= 0:
        violations["paged-quant:bytes_saved"] = (
            counters.get("serving.kv.quant.bytes_saved"), ">0")
    qsbefore = counters.snapshot()
    pq_run(pq_q)
    qsteady = counters.delta(qsbefore)
    for k in ("serving.retraces", "jit.traces", "jit.hydrates",
              "jit.syncs", "kernels.paged.xla_fallbacks"):
        if qsteady.get(k, 0):
            violations[f"paged-quant:{k}"] = (qsteady.get(k, 0), 0)

    # the documented logit-tolerance gate, direct-call: quantized-KV
    # prefill logits and PTQ-int8 weights vs the fp32 reference
    QUANT_LOGIT_TOL = 0.05
    sw = smodel.decode_state()
    L_, nh_ = scfg.num_layers, scfg.num_heads
    hd_ = scfg.hidden_size // scfg.num_heads
    sdt = jnp.dtype(scfg.dtype)
    qids = jnp.asarray(rng.randint(0, 64, size=(1, 16)), jnp.int32)
    qbt = jnp.arange(4, dtype=jnp.int32)                # 16 tokens, bs=4
    _, _, ref_logits = smodel.prefill_paged(
        sw, qids, 0, 16, qbt,
        jnp.zeros((L_, 4, 4, nh_, hd_), sdt),
        jnp.zeros((L_, 4, 4, nh_, hd_), sdt))
    ref_l = np.asarray(ref_logits)
    quant_drift = {}
    for kvd in ("int8", "fp8"):
        adt = _pa.KV_DTYPES[kvd]
        out = smodel.prefill_paged(
            sw, qids, 0, 16, qbt,
            jnp.zeros((L_, 4, 4, nh_, hd_), adt),
            jnp.zeros((L_, 4, 4, nh_, hd_), adt),
            jnp.zeros((L_, 4, 4), jnp.float32),
            jnp.zeros((L_, 4, 4), jnp.float32))
        drift = float(np.abs(np.asarray(out[-1]) - ref_l).max())
        quant_drift[f"kv_{kvd}"] = drift
        if drift > QUANT_LOGIT_TOL * float(np.abs(ref_l).max()):
            violations[f"paged-quant:{kvd}_logits"] = (
                drift, f"<={QUANT_LOGIT_TOL}*max|ref|")
    _, _, ptq_logits = smodel.prefill_paged(
        ptq_int8_decode_state(smodel), qids, 0, 16, qbt,
        jnp.zeros((L_, 4, 4, nh_, hd_), sdt),
        jnp.zeros((L_, 4, 4, nh_, hd_), sdt))
    ptq_drift = float(np.abs(np.asarray(ptq_logits) - ref_l).max())
    quant_drift["ptq_int8"] = ptq_drift
    if ptq_drift > QUANT_LOGIT_TOL * float(np.abs(ref_l).max()):
        violations["paged-quant:ptq_logits"] = (
            ptq_drift, f"<={QUANT_LOGIT_TOL}*max|ref|")

    # ---- speculative gate: draft/verify fixed-shape economics -----------
    # Greedy speculative output is token-identical to the non-spec paged
    # engine for ANY draft model; a warm measured window dispatches only
    # CACHED programs — zero retraces / traces / hydrates / syncs — and
    # the engine's whole lifetime compiled exactly ONE draft decode
    # program and ONE verify program (the one-program/zero-steady-retrace
    # economics); the acceptance ledger balances exactly every round:
    # accepted + rejected == drafted, K+1 draft launches + ONE verify.
    from paddle_tpu.serving.paged import _model_programs
    from paddle_tpu.serving.kvcache import blocks_for_tokens

    paddle.seed(7)
    sdraft = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=4,
        max_seq_len=32, use_flash_attention=False))
    sdraft.eval()
    SPEC_K = 2
    SPEC_NB = 2 * 2 * blocks_for_tokens(32, 4) + 1   # both namespaces

    def spec_engine():
        # prefix cache off so warm and measured runs chunk identically
        return LLMEngine(smodel, draft_model=sdraft, spec_k=SPEC_K,
                         max_slots=2, max_seq_len=32,
                         min_bucket=4, block_size=4, prefill_chunk=8,
                         n_blocks=SPEC_NB, prefix_cache=False)

    sp_eng = spec_engine()
    sp_greedy = pq_run(sp_eng)    # warm: compiles draft + verify programs
    if sp_greedy != base_greedy:
        violations["spec:greedy_identity"] = (sp_greedy, base_greedy)
    spbefore = counters.snapshot()
    sp_greedy2 = pq_run(sp_eng)   # measured: every program cached
    spsteady = counters.delta(spbefore)
    if sp_greedy2 != base_greedy:
        violations["spec:greedy_identity_warm"] = (sp_greedy2, base_greedy)
    for k in ("serving.retraces", "jit.traces", "jit.hydrates",
              "jit.syncs"):
        if spsteady.get(k, 0):
            violations[f"spec:{k}"] = (spsteady.get(k, 0), 0)
    sp_drafted = spsteady.get("serving.spec.drafted", 0)
    if not sp_drafted:
        violations["spec:drafted"] = (sp_drafted, ">0")
    sp_balance = (spsteady.get("serving.spec.accepted", 0)
                  + spsteady.get("serving.spec.rejected", 0))
    if sp_balance != sp_drafted:
        violations["spec:ledger"] = (sp_balance, sp_drafted)
    sp_rounds = spsteady.get("serving.spec.verify_steps", 0)
    if (not sp_rounds or spsteady.get("serving.spec.draft_steps", 0)
            != (SPEC_K + 1) * sp_rounds):
        violations["spec:round_dispatches"] = (
            spsteady.get("serving.spec.draft_steps", 0),
            f"{SPEC_K + 1} * {sp_rounds}")
    spec_dkeys = [k for k in _model_programs(sdraft) if isinstance(k, str)
                  and k.startswith("serving.draft_paged")]
    spec_vkeys = [k for k in _model_programs(smodel) if isinstance(k, str)
                  and k.startswith("serving.verify_paged")]
    if len(spec_dkeys) != 1:
        violations["spec:draft_programs"] = (spec_dkeys, 1)
    if len(spec_vkeys) != 1:
        violations["spec:verify_programs"] = (spec_vkeys, 1)

    # ---- elastic-fleet gate: zero lost under churn, warm replicas -------
    from paddle_tpu.resilience import faultinject
    from paddle_tpu.serving import ServingFleet

    FLEET_LENS = (3, 4)   # one shared bucket {4}: one warmup compile/engine
    fleet_prompts = [rng.randint(0, 64, size=n).tolist()
                     for n in FLEET_LENS]
    frefs = []
    for p in fleet_prompts:   # single-engine reference trajectories
        h = peng.add_request(p, max_new_tokens=3)
        while not h.is_finished:
            peng.step()
        frefs.append(list(h.tokens))

    fleet = ServingFleet(smodel, replicas=2, max_slots=2, max_seq_len=32,
                         min_bucket=4, threaded=False,
                         warm_buckets=FLEET_LENS)
    # steady state: the no-fault fleet is token-identical to the single
    # engine and retraces NOTHING (every replica pre-compiled its buckets)
    flbefore = counters.snapshot()
    fhs = [fleet.submit(p, max_new_tokens=3) for p in fleet_prompts]
    fleet.join(fhs)
    flsteady = counters.delta(flbefore)
    flinvariants = {
        "serving.retraces": 0,
        "jit.traces": 0,
        "serving.fleet.dispatched": len(FLEET_LENS),
        "serving.fleet.shed": 0,
        "serving.fleet.lost": 0,
    }
    violations.update({f"fleet:{k}": (flsteady.get(k, 0), want)
                       for k, want in flinvariants.items()
                       if flsteady.get(k, 0) != want})
    for h, ref in zip(fhs, frefs):
        if list(h.tokens) != ref or h.finish_reason != "length":
            violations[f"fleet:identity@{h.rid}"] = (list(h.tokens), ref)

    # churn: kill the replica decoding the first request; it must be
    # replayed onto a survivor — zero lost, respawns == injected faults,
    # and the delivered tokens still match the single-engine reference
    chbefore = counters.snapshot()
    chs = [fleet.submit(p, max_new_tokens=3) for p in fleet_prompts]
    with faultinject.fault_schedule(f"replica_crash@{chs[0].rid}"):
        fleet.join(chs)
    fleet.drain()
    chsteady = counters.delta(chbefore)
    chinvariants = {
        "serving.fleet.lost": 0,                 # THE durability gate
        "serving.fleet.respawns": 1,             # == injected faults
        "serving.fleet.retried": 1,
        "serving.fleet.replica_deaths.crash": 1,
        "serving.fleet.replica_deaths": 1,
    }
    violations.update({f"fleet-churn:{k}": (chsteady.get(k, 0), want)
                       for k, want in chinvariants.items()
                       if chsteady.get(k, 0) != want})
    for h, ref in zip(chs, frefs):
        if list(h.tokens) != ref or h.finish_reason != "length":
            violations[f"fleet-churn:identity@{h.rid}"] = (list(h.tokens),
                                                           ref)

    # ---- disagg gate: block-granular migration economics ----------------
    # A 1 prefill + 1 decode split must (a) stay token-identical to the
    # unified paged fleet, (b) copy EXACTLY the owned non-shared blocks
    # on every hand-off — blocks_copied == sum(blocks_for_tokens(len)) -
    # blocks_shared, with a block-aligned common prefix resolved against
    # the decode replica's radix tree instead of moved again — and
    # (c) retrace nothing once the warm pass has compiled the migration
    # gather alongside the usual bucket programs.
    DIS_BS = 4
    DIS_LENS = (9, 9)
    dis_p1 = rng.randint(0, 64, size=DIS_LENS[0]).tolist()
    # same 2-block (8-token) prefix, divergent tail: the second hand-off
    # must share those 2 blocks and copy only its owned tail block
    dis_p2 = dis_p1[:8] + [(dis_p1[8] + 1) % 64]
    dis_prompts = [dis_p1, dis_p2]

    def disagg_fleet(prefill_replicas):
        return ServingFleet(smodel, replicas=2,
                            prefill_replicas=prefill_replicas,
                            max_slots=2, max_seq_len=32, min_bucket=4,
                            threaded=False,
                            block_size=DIS_BS, n_blocks=64,
                            prefill_chunk=8, warm_buckets=DIS_LENS)

    ufleet = disagg_fleet(0)   # unified paged reference, same prompts
    drefs = []
    for p in dis_prompts:
        h = ufleet.submit(p, max_new_tokens=3)
        ufleet.join([h])
        drefs.append(list(h.tokens))
    ufleet.drain()

    dfleet = disagg_fleet(1)
    for p in dis_prompts:      # warm pass: compiles the migrate program
        dfleet.join([dfleet.submit(
            rng.randint(0, 64, size=len(p)).tolist(), max_new_tokens=3)])
    for rep in dfleet._replicas:   # measured hand-offs stay prefix-cold
        if rep.engine.prefix is not None:
            rep.engine.prefix.clear()
    dbefore = counters.snapshot()
    dhs = []
    for p in dis_prompts:      # sequential: p1 donates before p2 lands
        h = dfleet.submit(p, max_new_tokens=3)
        dfleet.join([h])
        dhs.append(h)
    dsteady = counters.delta(dbefore)
    dfleet.drain()
    owned = sum(blocks_for_tokens(len(p), DIS_BS) for p in dis_prompts)
    dinvariants = {
        "serving.retraces": 0,
        "jit.traces": 0,
        "serving.fleet.lost": 0,
        "serving.fleet.migrate.requests": len(dis_prompts),
        "serving.fleet.migrate.blocks_shared": 2,
        "serving.fleet.migrate.blocks_copied": owned - 2,
    }
    violations.update({f"disagg:{k}": (dsteady.get(k, 0), want)
                       for k, want in dinvariants.items()
                       if dsteady.get(k, 0) != want})
    for h, ref in zip(dhs, drefs):
        if list(h.tokens) != ref or h.finish_reason != "length":
            violations[f"disagg:identity@{h.rid}"] = (list(h.tokens), ref)

    # ---- tiering gate: host-RAM KV tier economics -----------------------
    # An oversubscribed paged engine (pool far smaller than the working
    # set) backed by a host tier must (a) stay token-identical — greedy
    # AND seeded sampling — to the ample-pool engine on the same
    # prompts, (b) reach an allocation-free steady state: measured
    # spill/restore churn with ZERO retraces/traces/syncs and a FLAT
    # host arena (every buffer served by the reuse pool), and (c)
    # degrade a dropped host copy (kv_spill_drop) to a deterministic
    # cache-miss replay with both tiers reconciled.
    TIER_PROMPTS = [rng.randint(0, 64, size=9).tolist() for _ in range(6)]

    def tier_run(eng_, sampled=False):
        outs = []
        for i, p in enumerate(TIER_PROMPTS):   # sequential: each finished
            h = eng_.add_request(p, max_new_tokens=4, seed=21 + i,
                                 **(pq_sample if sampled else {}))
            while not h.is_finished:           # seq donates, then the next
                eng_.step()                    # admission forces spills
            outs.append(list(h.tokens))
        return outs

    tbase = pq_engine(n_blocks=64)             # ample pool: never spills
    tier_greedy = tier_run(tbase)
    tier_sampled = tier_run(tbase, sampled=True)

    teng = pq_engine(n_blocks=8, host_kv_blocks=64)   # 7 usable blocks
    tier_run(teng)                  # warm: compiles spill/restore programs
    tier_run(teng, sampled=True)    # ...and fills the buffer reuse pool
    tbefore = counters.snapshot()
    t_greedy = tier_run(teng)
    t_sampled = tier_run(teng, sampled=True)
    tsteady = counters.delta(tbefore)
    if t_greedy != tier_greedy:
        violations["tiering:greedy_identity"] = (t_greedy, tier_greedy)
    if t_sampled != tier_sampled:
        violations["tiering:sampled_identity"] = (t_sampled, tier_sampled)
    for k in ("serving.retraces", "jit.traces", "jit.hydrates",
              "jit.syncs"):
        if tsteady.get(k, 0):
            violations[f"tiering:{k}"] = (tsteady.get(k, 0), 0)
    for k in ("serving.kv.tier.spilled_blocks",
              "serving.kv.tier.restored_blocks",
              "serving.kv.host_buf_reuse"):
        if tsteady.get(k, 0) <= 0:
            violations[f"tiering:{k}"] = (tsteady.get(k, 0), ">0")
    # the no-malloc gate: a warm tier serves every spill/restore buffer
    # from the reuse pool — the pinned arena never grows
    if tsteady.get("serving.kv.host_arena_bytes", 0):
        violations["tiering:host_arena_growth"] = (
            tsteady.get("serving.kv.host_arena_bytes", 0), 0)

    # chaos leg: re-establish the victim chain (the churn may have
    # evicted it outright), force it host-resident, then drop its host
    # copy mid-restore — admission degrades to a plain prefix miss and
    # the replayed prefill is token-identical
    th0 = teng.add_request(TIER_PROMPTS[0], max_new_tokens=4, seed=21)
    while not th0.is_finished:
        teng.step()
    with teng._cond:
        teng._spill_cold(32)
    if teng.prefix_probe(np.asarray(TIER_PROMPTS[0], np.int32))[1] <= 0:
        violations["tiering-chaos:victim_not_host"] = (
            teng.prefix_probe(np.asarray(TIER_PROMPTS[0], np.int32)), ">0")
    tdbefore = counters.snapshot()
    th = teng.add_request(TIER_PROMPTS[0], max_new_tokens=4, seed=21)
    with faultinject.fault_schedule(f"kv_spill_drop@{th.rid}"):
        while not th.is_finished:
            teng.step()
    tdrop = counters.delta(tdbefore)
    if list(th.tokens) != tier_greedy[0]:
        violations["tiering-chaos:identity"] = (list(th.tokens),
                                                tier_greedy[0])
    if tdrop.get("resilience.faults_injected.kv_spill_drop", 0) != 1:
        violations["tiering-chaos:faults"] = (
            tdrop.get("resilience.faults_injected.kv_spill_drop", 0), 1)
    if tdrop.get("serving.kv.tier.spill_drops", 0) <= 0:
        violations["tiering-chaos:spill_drops"] = (
            tdrop.get("serving.kv.tier.spill_drops", 0), ">0")
    t_live = sum(1 for b in range(1, len(teng.pool._ref))
                 if teng.pool._ref[b] > 0)
    if len(teng.pool._free) + t_live != teng.pool.capacity:
        violations["tiering-chaos:pool_leak"] = (
            len(teng.pool._free) + t_live, teng.pool.capacity)

    # ---- resilience gate 1: saves cost ONE sync each, nothing else ------
    import tempfile
    from paddle_tpu.resilience import (CheckpointManager,
                                       FaultTolerantTrainer)

    CKPT_SAVES = 2
    CKPT_STEPS_PER_SAVE = 2
    paddle.seed(0)
    cmodel = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
    copt = paddle.optimizer.AdamW(1e-3, parameters=cmodel.parameters())
    cstep = pjit.CompiledTrainStep(cmodel, loss_fn, copt)
    for _ in range(WARMUP):
        cstep(x, y).numpy()
    with tempfile.TemporaryDirectory() as ckdir:
        mgr = CheckpointManager(ckdir, keep_last=2)
        cbefore = counters.snapshot()
        for i in range(CKPT_SAVES):
            for _ in range(CKPT_STEPS_PER_SAVE):
                cstep(x, y).numpy()
            mgr.save(cstep, (i + 1) * CKPT_STEPS_PER_SAVE, blocking=True)
        csteady = counters.delta(cbefore)

    ckpt_steps = CKPT_SAVES * CKPT_STEPS_PER_SAVE
    cinvariants = {
        "jit.traces": 0,
        "jit.hydrates": 0,
        "jit.cache_misses": 0,
        "jit.steps": ckpt_steps,
        "jit.host.dispatches": ckpt_steps,
        "resilience.saves": CKPT_SAVES,
        # THE budget: one counter-gated sync per save, nothing more
        "jit.syncs": CKPT_SAVES,
        "jit.host.bind_layer_state": CKPT_SAVES,
        "jit.host.bind_optimizer_state": CKPT_SAVES,
        "jit.host.layer_state": 0,
        "jit.host.optimizer_state": 0,
    }
    violations.update({f"ckpt:{k}": (csteady.get(k, 0), want)
                       for k, want in cinvariants.items()
                       if csteady.get(k, 0) != want})

    # ---- resilience gate 2: restores == injected preemptions ------------
    from paddle_tpu.io import DataLoader, TensorDataset

    FAULT_STEPS = 6
    FAULT_SCHEDULE = "preempt@3"
    INJECTED_PREEMPTIONS = 1
    paddle.seed(0)
    rmodel = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
    ropt = paddle.optimizer.AdamW(1e-3, parameters=rmodel.parameters())
    rstep = pjit.CompiledTrainStep(rmodel, loss_fn, ropt)
    rx = np.random.RandomState(1)
    ds = TensorDataset(
        [paddle.to_tensor(rx.randn(FAULT_STEPS * 4, 16).astype("float32")),
         paddle.to_tensor(rx.randn(FAULT_STEPS * 4, 4).astype("float32"))])

    def loader_factory(epoch):
        return DataLoader(ds, batch_size=4, shuffle=False)

    rbefore = counters.snapshot()
    with tempfile.TemporaryDirectory() as ckdir:
        with faultinject.fault_schedule(FAULT_SCHEDULE):
            trainer = FaultTolerantTrainer(
                rstep, loader_factory, CheckpointManager(ckdir, keep_last=2),
                epochs=1, max_steps=FAULT_STEPS, save_every=3)
            rlosses = trainer.run()
    rsteady = counters.delta(rbefore)

    rinvariants = {
        "resilience.restores": INJECTED_PREEMPTIONS,
        "resilience.recoveries": INJECTED_PREEMPTIONS,
        "resilience.faults_injected.preempt": INJECTED_PREEMPTIONS,
        "resilience.corrupt_detected": 0,
        "resilience.save_failures": 0,
    }
    violations.update({f"fault:{k}": (rsteady.get(k, 0), want)
                       for k, want in rinvariants.items()
                       if rsteady.get(k, 0) != want})
    if len(rlosses) != FAULT_STEPS or not all(
            np.isfinite(v) for v in rlosses.values()):
        violations["fault:trainer_losses"] = (len(rlosses), FAULT_STEPS)

    # ---- metrics-parity gate: telemetry ON adds ZERO syncs / traces /
    # dispatches / retraces to any steady-state phase.  Fresh objects per
    # run so OFF and ON each pay the same warmup; the ON run harvests
    # (metrics_flush / prometheus_text) INSIDE the measured window — the
    # read path must be free too.
    from paddle_tpu.profiler import metrics as pmetrics

    PARITY_KEYS = ("jit.syncs", "jit.traces", "jit.host.dispatches",
                   "serving.retraces")

    def _pick(d):
        return {k: d.get(k, 0) for k in PARITY_KEYS}

    def train_phase(m):
        paddle.seed(0)
        tm = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
        topt = paddle.optimizer.AdamW(1e-3, parameters=tm.parameters())
        ts = pjit.CompiledTrainStep(tm, loss_fn, topt,
                                    metrics=True if m else None)
        for _ in range(WARMUP):
            ts(x, y).numpy()
        b = counters.snapshot()
        for _ in range(MEASURE):
            ts(x, y).numpy()
        if m:
            ts.metrics_flush()
        return _pick(counters.delta(b))

    def fused_phase(m):
        paddle.seed(0)
        tm = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
        topt = paddle.optimizer.AdamW(1e-3, parameters=tm.parameters())
        ts = pjit.CompiledTrainStep(tm, loss_fn, topt, fused_steps=FUSED_K,
                                    metrics=True if m else None)
        ts(window()).numpy()  # priming single-step fallback
        ts(window()).numpy()  # scan compile
        b = counters.snapshot()
        for _ in range(FUSED_MEASURE):
            ts(window()).numpy()
        if m:
            ts.metrics_flush()
        return _pick(counters.delta(b))

    def mesh_phase(m):
        from jax.sharding import Mesh as _Mesh
        mesh2 = _Mesh(np.array(jax.devices()[:2]).reshape(2), ("dp",))
        paddle.seed(0)
        tm = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
        topt = paddle.optimizer.AdamW(1e-3, parameters=tm.parameters())
        ts = pjit.CompiledTrainStep(tm, loss_fn, topt, mesh=mesh2,
                                    metrics=True if m else None)
        for _ in range(WARMUP):
            ts(x, y).numpy()
        b = counters.snapshot()
        for _ in range(MEASURE):
            ts(x, y).numpy()
        if m:
            ts.metrics_flush()
        return _pick(counters.delta(b))

    def serve_phase(m):
        paddle.seed(0)
        e2 = LLMEngine(smodel, max_slots=2, max_seq_len=32, min_bucket=4)
        rng2 = np.random.RandomState(7)

        def sv(lens):
            hs = [e2.add_request(rng2.randint(0, 64, size=n).tolist(),
                                 max_new_tokens=3) for n in lens]
            while not all(h.is_finished for h in hs):
                e2.step()
                if m:   # harvesting telemetry mid-serve must be free
                    pmetrics.prometheus_text()
                    pmetrics.histogram_summaries()

        sv(SERVE_LENS_WARM)
        b = counters.snapshot()
        sv(SERVE_LENS_MEASURE)
        return _pick(counters.delta(b))

    parity_phases = [("train", train_phase), ("fused", fused_phase),
                     ("serving", serve_phase)]
    if jax.device_count() >= 2:
        parity_phases.append(("mesh", mesh_phase))
    metrics_parity = {}
    for pname, pfn in parity_phases:
        off, on = pfn(False), pfn(True)
        metrics_parity[pname] = {"off": off, "on": on}
        if on != off:
            violations[f"metrics-parity:{pname}"] = (on, off)

    # ---- trace gate: request tracing OFF is zero-overhead (no trace.*
    # movement, counter-identical parity keys vs the ON run of the same
    # fresh workload); ON, every finished engine request's stage spans
    # must account its measured TTFT + decode wall time.
    from paddle_tpu.core import flags as pflags
    from paddle_tpu.profiler import trace as rtrace

    def trace_workloads():
        """A fresh engine + a sync fleet over identical deterministic
        workloads; returns (delta, engine handles)."""
        paddle.seed(0)
        rngt = np.random.RandomState(11)
        p3 = LLMEngine(smodel, max_slots=2, max_seq_len=32, min_bucket=4,
                       block_size=4, prefill_chunk=8)

        def sv(e_, lens):
            hs = [e_.add_request(rngt.randint(0, 64, size=n).tolist(),
                                 max_new_tokens=3) for n in lens]
            while not all(h.is_finished for h in hs):
                e_.step()
            return hs

        sv(p3, SERVE_LENS_WARM)
        fl3 = ServingFleet(smodel, replicas=2, max_slots=2, max_seq_len=32,
                           min_bucket=4, threaded=False,
                           warm_buckets=SERVE_LENS_WARM)
        b = counters.snapshot()
        hs = sv(p3, SERVE_LENS_MEASURE)
        fhs3 = [fl3.submit(rngt.randint(0, 64, size=n).tolist(),
                           max_new_tokens=3) for n in SERVE_LENS_MEASURE]
        fl3.join(fhs3)
        d = counters.delta(b)
        fl3.drain()
        return d, hs, fhs3

    pflags.set_flags({"FLAGS_request_trace_sample": 0.0})
    toff, _, _ = trace_workloads()
    off_moved = {k: v for k, v in toff.items()
                 if k.startswith("trace.") and v}
    if off_moved:
        violations["trace-off:counters"] = (off_moved, {})
    pflags.set_flags({"FLAGS_request_trace_sample": 1.0})
    try:
        ton, ths, tfhs = trace_workloads()
    finally:
        pflags.set_flags({"FLAGS_request_trace_sample": 0.0})
    for k in PARITY_KEYS:
        if ton.get(k, 0) != toff.get(k, 0):
            violations[f"trace-parity:{k}"] = (ton.get(k, 0),
                                               toff.get(k, 0))
    # every measured request (2 engine + 2 fleet) finalized a trace
    if ton.get("trace.finished", 0) < len(ths) + len(tfhs):
        violations["trace-on:finished"] = (
            ton.get("trace.finished", 0), f">={len(ths) + len(tfhs)}")
    # span accounting: stage spans (queue + prefill + decode) sum within
    # loose tolerance of the measured arrival -> last-emit wall clock;
    # the lower bound allows the other slot's prefill to interleave, the
    # upper allows queue/kv.reserve overlap in the admit path
    trace_ratios = {}
    for h in ths:
        measured = max(1, (h.last_emit_ns or h.arrival_ns) - h.arrival_ns)
        ratio = sum(h.trace.stage_ns().values()) / measured
        trace_ratios[f"r{h.rid}"] = round(ratio, 3)
        if not 0.2 <= ratio <= 1.3:
            violations[f"trace-span-sum:r{h.rid}"] = (round(ratio, 3),
                                                      "[0.2, 1.3]")
    rtrace.clear()

    # ---- health gate: the health plane is zero-overhead OFF (no
    # health.* movement, counter-identical parity keys vs the ON run of
    # the same fresh train + engine + fleet workload), fires ZERO
    # alerts on clean ON legs, fires EXACTLY the expected alert under
    # injected chaos (slow_decode -> itl_burn, kv_pool_exhausted ->
    # kv_backpressure) with a postmortem dump naming the rule + window,
    # and the admission recommendation reaches Router.stats() plus the
    # live /alerts /slo /signals endpoints.
    import urllib.request

    from paddle_tpu.profiler import flight as pflight
    from paddle_tpu.profiler import health as phealth
    from paddle_tpu.profiler.ops import OpsServer

    def health_workloads():
        """Fresh train step + an engine (standalone monitor, first
        tick post-warm) + a sync fleet (self-ticking from pump);
        returns the measured counter delta."""
        paddle.seed(0)
        rngh = np.random.RandomState(13)
        hm = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
        hopt = paddle.optimizer.AdamW(1e-3, parameters=hm.parameters())
        hstep = pjit.CompiledTrainStep(hm, loss_fn, hopt)
        p5 = LLMEngine(smodel, max_slots=2, max_seq_len=32, min_bucket=4,
                       block_size=4, prefill_chunk=8)
        mon = phealth.HealthMonitor(interval_s=0.0).attach(p5)

        def sv(e_, lens, tick=False):
            hs = [e_.add_request(rngh.randint(0, 64, size=n).tolist(),
                                 max_new_tokens=3) for n in lens]
            while not all(h.is_finished for h in hs):
                e_.step()
                if tick:    # live monitoring mid-serve must be free
                    mon.maybe_tick()
            return hs

        for _ in range(WARMUP):
            hstep(x, y).numpy()
        sv(p5, SERVE_LENS_WARM)
        fl5 = ServingFleet(smodel, replicas=2, max_slots=2, max_seq_len=32,
                           min_bucket=4, threaded=False,
                           warm_buckets=SERVE_LENS_WARM)
        b = counters.snapshot()
        for _ in range(MEASURE):
            hstep(x, y).numpy()
        mon.maybe_tick()
        sv(p5, SERVE_LENS_MEASURE, tick=True)
        fhs = [fl5.submit(rngh.randint(0, 64, size=n).tolist(),
                          max_new_tokens=3) for n in SERVE_LENS_MEASURE]
        fl5.join(fhs)
        d = counters.delta(b)
        fl5.drain()
        return d

    pflags.set_flags({"FLAGS_health": False})
    hoff = health_workloads()
    hoff_moved = {k: v for k, v in hoff.items()
                  if k.startswith("health.") and v}
    if hoff_moved:
        violations["health-off:counters"] = (hoff_moved, {})
    pflags.set_flags({"FLAGS_health": True, "FLAGS_health_interval_s": 0.0})
    try:
        hon = health_workloads()
        for k in PARITY_KEYS:
            if hon.get(k, 0) != hoff.get(k, 0):
                violations[f"health-parity:{k}"] = (hon.get(k, 0),
                                                    hoff.get(k, 0))
        hclean_fired = {k: v for k, v in hon.items()
                        if k.startswith("health.alerts.fired") and v}
        if hclean_fired:
            violations["health-clean:alerts"] = (hclean_fired, {})
        if not hon.get("health.ticks"):
            violations["health-on:ticks"] = (hon.get("health.ticks", 0),
                                             ">=1")

        # chaos leg 1: a stalled decode loop must trip the fast+slow ITL
        # burn windows of the fleet's own monitor — and nothing else
        rngh6 = np.random.RandomState(17)
        fl6 = ServingFleet(smodel, replicas=2, max_slots=2, max_seq_len=32,
                           min_bucket=4, threaded=False,
                           warm_buckets=SERVE_LENS_WARM,
                           heartbeat_timeout_s=30.0)

        def settle6(deadline_s=15.0):
            """Tick until nothing is firing — a loaded CI box can push
            nominal ITL over the CPU-scale burn target; once traffic
            stops the windows drain and spurious alerts resolve.  The
            router refuses shed=True admissions while critical, so the
            next leg must not start until the plane is quiet."""
            t0 = time.monotonic()
            while time.monotonic() - t0 < deadline_s:
                fl6.health.maybe_tick()
                if not fl6.health.firing():
                    return True
                time.sleep(0.05)
            return False

        # clean leg on the same fleet: silence.  Retried (re-baselined)
        # on a box hiccup so the chaos expectation below stays exact.
        for _ in range(3):
            b = counters.snapshot()
            chs6 = [fl6.submit(rngh6.randint(0, 64, size=3).tolist(),
                               max_new_tokens=6) for _ in range(4)]
            fl6.join(chs6)
            hclean6 = {k: v for k, v in counters.delta(b).items()
                       if k.startswith("health.alerts.fired.") and v}
            if not hclean6:
                break
            settle6()
        if hclean6:
            violations["health-chaos:clean-leg"] = (hclean6, {})
        chs6 = [fl6.submit(rngh6.randint(0, 64, size=3).tolist(),
                           max_new_tokens=8) for _ in range(4)]
        with faultinject.fault_schedule(f"slow_decode@{chs6[0].rid}*8"):
            fl6.join(chs6)
        hfired = {k: v for k, v in counters.delta(b).items()
                  if k.startswith("health.alerts.fired.")}
        if hfired != {"health.alerts.fired.itl_burn": 1}:
            violations["health-chaos:slow_decode"] = (
                hfired, {"health.alerts.fired.itl_burn": 1})
        hb = pflight.load(pflight.last_dump_path())
        hdump = (hb.get("reason"),
                 (hb.get("context") or {}).get("rule"),
                 bool(((hb.get("context") or {}).get("window") or {})
                      .get("seconds")))
        if hdump != ("health_itl_burn", "itl_burn", True):
            violations["health-chaos:slow_decode-dump"] = (
                hdump, ("health_itl_burn", "itl_burn", True))
        # the recommendation must reach the router and the live ops
        # endpoints while the alert is still firing
        hadm = fl6.router.stats()["health"]["admission_level"]
        if hadm != "critical":
            violations["health-chaos:admission"] = (hadm, "critical")
        ops_live = {}
        with OpsServer(fleet=fl6) as srv:
            for ep in ("/alerts", "/slo", "/signals", "/healthz"):
                body = json.loads(urllib.request.urlopen(
                    srv.url(ep), timeout=10).read())
                ops_live[ep] = sorted(body)[:4]
                if ep == "/alerts" and body.get("firing") != ["itl_burn"]:
                    violations["health-ops:alerts"] = (body.get("firing"),
                                                       ["itl_burn"])
                if ep == "/healthz" and body.get("status") != "degraded":
                    violations["health-ops:healthz"] = (body.get("status"),
                                                        "degraded")
        fl6.drain()

        # chaos leg 2: refused block reservations must trip the KV
        # backpressure watchdog on a standalone paged engine (first tick
        # after warmup so compile activity stays outside every window)
        p6 = LLMEngine(smodel, max_slots=2, max_seq_len=32, min_bucket=4,
                       block_size=4, prefill_chunk=8)
        mon6 = phealth.HealthMonitor(
            rules=[wd for wd in phealth.default_watchdogs()
                   if wd.name in ("kv_backpressure", "kv_conservation")],
            interval_s=0.0).attach(p6)
        h0 = p6.add_request(rngh6.randint(0, 64, size=6).tolist(),
                            max_new_tokens=3)
        while not h0.is_finished:
            p6.step()
        mon6.maybe_tick()
        b = counters.snapshot()
        h1 = p6.add_request(rngh6.randint(0, 64, size=6).tolist(),
                            max_new_tokens=3)
        with faultinject.fault_schedule(f"kv_pool_exhausted@{h1.rid}"):
            for _ in range(300):
                p6.step()
                mon6.maybe_tick()
                if h1.is_finished:
                    break
        kfired = {k: v for k, v in counters.delta(b).items()
                  if k.startswith("health.alerts.fired.")}
        if kfired != {"health.alerts.fired.kv_backpressure": 1}:
            violations["health-chaos:kv_pool_exhausted"] = (
                kfired, {"health.alerts.fired.kv_backpressure": 1})
        kb = pflight.load(pflight.last_dump_path())
        kwin = (kb.get("context") or {}).get("window") or {}
        kdump = (kb.get("reason"),
                 (kwin.get("delta") or {}).get("serving.kv.pool_exhausted",
                                               0) >= 1)
        if kdump != ("health_kv_backpressure", True):
            violations["health-chaos:kv-dump"] = (
                kdump, ("health_kv_backpressure", True))
    finally:
        pflags.set_flags({"FLAGS_health": False,
                          "FLAGS_health_interval_s": 1.0})

    # ---- program-audit gate: FLAGS_program_audit=enforce holds over the
    # whole compiled-program surface (train single/fused/mesh-dp2 +
    # serving incl. the COW copy program) with zero findings,
    # and audit ON adds ZERO syncs/traces/dispatches/retraces to any
    # measured steady-state window — audits run once per program, at the
    # compile/warmup sites.  Then each deliberately-broken fixture must be
    # caught and named by rule.
    from paddle_tpu import analysis as panalysis

    def audit_workloads():
        """Fresh train steps (metrics / fused / mesh-dp2) + plain and
        speculative engines over fixed workloads.  All compiles (and
        audits, when on) happen before the snapshot; returns the measured
        parity delta."""
        panalysis.reset_audited()
        paddle.seed(0)
        am = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
        aopt = paddle.optimizer.AdamW(1e-3, parameters=am.parameters())
        astep = pjit.CompiledTrainStep(am, loss_fn, aopt, metrics=True)
        for _ in range(WARMUP):
            astep(x, y).numpy()
        paddle.seed(0)
        afm = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))
        afopt = paddle.optimizer.AdamW(1e-3, parameters=afm.parameters())
        afstep = pjit.CompiledTrainStep(afm, loss_fn, afopt,
                                        fused_steps=FUSED_K)
        afstep(window()).numpy()  # priming single-step fallback
        afstep(window()).numpy()  # scan compile
        amstep = None
        if jax.device_count() >= 2:
            from jax.sharding import Mesh as _Mesh
            # the mesh step program shares its name with the single-device
            # one — re-arm the once-per-name audit so it is audited too
            panalysis.reset_audited()
            amesh = _Mesh(np.array(jax.devices()[:2]).reshape(2), ("dp",))
            paddle.seed(0)
            amm = nn.Sequential(nn.Linear(16, 32), nn.GELU(),
                                nn.Linear(32, 4))
            amopt = paddle.optimizer.AdamW(1e-3,
                                           parameters=amm.parameters())
            amstep = pjit.CompiledTrainStep(amm, loss_fn, amopt, mesh=amesh)
            for _ in range(WARMUP):
                amstep(x, y).numpy()
        p4 = LLMEngine(smodel, max_slots=2, max_seq_len=32, min_bucket=4,
                       block_size=4, prefill_chunk=8)
        rng4 = np.random.RandomState(7)

        def sv(e_, lens):
            hs = [e_.add_request(rng4.randint(0, 64, size=n).tolist(),
                                 max_new_tokens=3) for n in lens]
            while not all(h.is_finished for h in hs):
                e_.step()
            return hs

        ah0 = sv(p4, SERVE_LENS_WARM)[0]
        # compile (and audit) the COW copy program at warmup: extend a
        # cached sequence past its partial prefix block
        acw = (list(ah0.prompt) + ah0.tokens)[:5] + [int(ah0.prompt[0])]
        ahc = p4.add_request(acw, max_new_tokens=3)
        while not ahc.is_finished:
            p4.step()
        # speculative engine: audits the draft-prefill chunk, draft
        # decode and verify programs at their compile/warmup sites
        sp4 = LLMEngine(smodel, draft_model=sdraft, spec_k=SPEC_K,
                        max_slots=2, max_seq_len=32,
                        min_bucket=4, block_size=4, prefill_chunk=8,
                        n_blocks=SPEC_NB, prefix_cache=False)
        sv(sp4, SERVE_LENS_WARM)

        b = counters.snapshot()
        for _ in range(MEASURE):
            astep(x, y).numpy()
        for _ in range(FUSED_MEASURE):
            afstep(window()).numpy()
        if amstep is not None:
            for _ in range(MEASURE):
                amstep(x, y).numpy()
        sv(p4, SERVE_LENS_MEASURE)
        sv(sp4, SERVE_LENS_MEASURE)
        return _pick(counters.delta(b))

    pflags.set_flags({"FLAGS_program_audit": "off"})
    audit_off = audit_workloads()
    pflags.set_flags({"FLAGS_program_audit": "enforce"})
    abefore = counters.snapshot()
    try:
        # any finding raises ProgramAuditError straight out of run()
        audit_on = audit_workloads()
    finally:
        pflags.set_flags({"FLAGS_program_audit": "off"})
    audit_delta = counters.delta(abefore)
    if audit_on != audit_off:
        violations["audit-parity"] = (audit_on, audit_off)
    audits_run = audit_delta.get("analysis.audits", 0)
    # step x2 + window + mesh step; 2 chunk buckets + decode + COW copy;
    # the speculative engine's draft chunk, draft decode and verify
    if audits_run < 10:
        violations["audit:coverage"] = (audits_run, ">=10")
    if audit_delta.get("analysis.findings", 0):
        violations["audit:findings"] = (
            audit_delta.get("analysis.findings", 0), 0)

    # seeded-broken fixtures: the auditor must catch each one by name
    import jax.numpy as jnp

    def cb_prog(v):
        out = jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct(v.shape, v.dtype),
            v)
        return out + 1

    def drop_prog(v):   # donated (4,4) input, only a scalar output
        return jnp.sum(v)

    fixture_got = {}
    v4 = jnp.ones((4, 4), jnp.float32)
    rep = panalysis.audit_program("fixture.callback", jax.jit(cb_prog), v4)
    fixture_got["host-callback"] = sorted({f.rule for f in rep.findings})
    rep = panalysis.audit_program(
        "fixture.donation", jax.jit(drop_prog, donate_argnums=(0,)), v4,
        donate_argnums=(0,))
    fixture_got["donation-dropped"] = sorted({f.rule for f in rep.findings})
    from jax import export as jexport
    bdim = jexport.symbolic_shape("b, 4")
    rep = panalysis.audit_program(
        "fixture.dynamic", jax.jit(lambda z: z * 2),
        jax.ShapeDtypeStruct(bdim, jnp.float32), compile_program=False)
    fixture_got["dynamic-shape"] = sorted({f.rule for f in rep.findings})
    for want_rule, got_rules in fixture_got.items():
        if want_rule not in got_rules:
            violations[f"audit-fixture:{want_rule}"] = (got_rules,
                                                        want_rule)

    # ---- devicetime gate: the device-time ledger is zero-overhead OFF
    # (sample=0 moves NO jit.devicetime.* / program.* state and the run
    # is counter-identical on the parity keys vs the ON run of the same
    # fresh plain/spec workload); ON (sample=4) pays EXACTLY the
    # budgeted fences — sampled_syncs == ceil(dispatches / 4) over a
    # window anchored by devicetime.reset() — with token identity, zero
    # retraces, and a populated ledger whose MFU/roofline gauges survive
    # GET /programs and a bench_compare --attribute run that names the
    # dominant program.
    import contextlib
    import importlib.util
    import io as _io
    import tempfile

    from paddle_tpu.profiler import devicetime as pdt

    def dt_workloads():
        """Fresh plain + spec engines over the pq workload; warm
        first so every compile (and, under sampling, its first noted
        dispatches) stays outside the measured window, which is anchored
        by an explicit ledger reset."""
        paddle.seed(0)
        p7 = pq_engine()
        s7 = spec_engine()
        for eng7 in (p7, s7):
            pq_run(eng7)                      # warm: compiles cached
        pdt.reset()                           # anchor the sample window
        b = counters.snapshot()
        outs = [pq_run(eng7) for eng7 in (p7, s7)]
        return counters.delta(b), outs

    dt_off, dt_off_tokens = dt_workloads()
    dt_off_moved = {k: v for k, v in dt_off.items()
                    if k.startswith(("jit.devicetime.", "program.")) and v}
    if dt_off_moved:
        violations["devicetime-off:counters"] = (dt_off_moved, {})
    if dt_off_tokens != [base_greedy, base_greedy]:
        violations["devicetime-off:identity"] = (dt_off_tokens,
                                                 base_greedy)

    # AOT-capture FLOPs/HBM bytes for every program name once (telemetry
    # pass), then sample with telemetry back OFF but peaks kept so the
    # ledger's efficiency join has both sides to work with.
    dt_saved = {k: pflags.flag(k) for k in
                ("FLAGS_peak_tflops", "FLAGS_peak_hbm_gbps",
                 "FLAGS_device_telemetry", "FLAGS_device_time_sample")}
    pflags.set_flags({"FLAGS_device_telemetry": True,
                      "FLAGS_peak_tflops": 197.0,
                      "FLAGS_peak_hbm_gbps": 819.0})
    try:
        dt_workloads()
        pflags.set_flags({"FLAGS_device_telemetry": False,
                          "FLAGS_device_time_sample": 4})
        dt_on, dt_on_tokens = dt_workloads()
    finally:
        # telemetry + sampling restored here; the PEAK flags stay live
        # through the reads below (the efficiency join reads them at
        # snapshot time) and are restored at the end of the phase
        pflags.set_flags({
            "FLAGS_device_telemetry": dt_saved["FLAGS_device_telemetry"],
            "FLAGS_device_time_sample":
                dt_saved["FLAGS_device_time_sample"]})
    for k in PARITY_KEYS:
        if dt_on.get(k, 0) != dt_off.get(k, 0):
            violations[f"devicetime-parity:{k}"] = (dt_on.get(k, 0),
                                                    dt_off.get(k, 0))
    if dt_on_tokens != dt_off_tokens:
        violations["devicetime-on:identity"] = (dt_on_tokens,
                                                dt_off_tokens)
    dt_disp = dt_on.get("jit.devicetime.dispatches", 0)
    dt_syncs = dt_on.get("jit.devicetime.sampled_syncs", 0)
    if not dt_disp:
        violations["devicetime-on:dispatches"] = (dt_disp, ">0")
    if dt_syncs != -(-dt_disp // 4):
        violations["devicetime-on:sync_budget"] = (
            dt_syncs, f"ceil({dt_disp}/4)")

    # the ledger the measured ON window left behind (the flag observer
    # never resets it): rows present, at least one with a joined MFU
    dt_snap = pdt.snapshot()
    if not dt_snap["programs"]:
        violations["devicetime:ledger"] = (0, ">=1 program row")
    dt_mfu_rows = [p["name"] for p in dt_snap["programs"]
                   if p.get("mfu") is not None]
    if not dt_mfu_rows:
        violations["devicetime:mfu_rows"] = ([], ">=1 row with MFU")

    # the same table over the wire
    with OpsServer() as dsrv:
        with urllib.request.urlopen(dsrv.url("/programs"),
                                    timeout=10) as r:
            dt_http = json.loads(r.read())
    if len(dt_http.get("programs") or []) != len(dt_snap["programs"]):
        violations["devicetime:/programs"] = (
            len(dt_http.get("programs") or []), len(dt_snap["programs"]))
    if not [p for p in dt_http.get("programs") or []
            if p.get("mfu") is not None]:
        violations["devicetime:/programs-mfu"] = ([], ">=1 row with MFU")

    # per-program regression attribution: a synthetic candidate run that
    # regresses throughput while the dominant program's device-time
    # share grows must be attributed to that program by name
    dt_block = pdt.bench_block(top=8)
    dt_dominant = max(dt_block["programs"],
                      key=lambda n: dt_block["programs"][n].get("share")
                      or 0.0)
    bc_spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(os.path.dirname(__file__),
                                      "bench_compare.py"))
    bc_mod = importlib.util.module_from_spec(bc_spec)
    bc_spec.loader.exec_module(bc_mod)
    with tempfile.TemporaryDirectory() as td:
        cand_block = json.loads(json.dumps(dt_block))
        crow = cand_block["programs"][dt_dominant]
        crow["share"] = min(1.0, (crow.get("share") or 0.5) + 0.2)
        for i, legs in ((1, {"paged": {"tokens_per_sec": 100.0,
                                       "devicetime": dt_block}}),
                        (2, {"paged": {"tokens_per_sec": 70.0,
                                       "devicetime": cand_block}})):
            with open(os.path.join(td, f"BENCH_r{i:02d}.json"), "w") as f:
                json.dump({"rc": 0, "parsed": {"legs": legs}}, f)
        buf = _io.StringIO()
        with contextlib.redirect_stdout(buf):
            bc_mod.main(["--glob", os.path.join(td, "BENCH_r0*.json"),
                         "--attribute"])
        dt_attr_out = buf.getvalue()
    if dt_dominant not in dt_attr_out:
        violations["devicetime:attribution"] = (
            dt_attr_out.splitlines()[-6:], dt_dominant)
    pflags.set_flags({"FLAGS_peak_tflops": dt_saved["FLAGS_peak_tflops"],
                      "FLAGS_peak_hbm_gbps":
                          dt_saved["FLAGS_peak_hbm_gbps"]})
    pdt.reset()

    # ---- mesh-serving gate: tensor-parallel paged decode over the
    # StateArena.  An mp2 engine must be token-identical to the
    # single-device engine (greedy AND seeded), hold the zero-steady-
    # retrace/hydrate/sync economics with dispatch counts unchanged,
    # carry the KV pool genuinely head-sharded per chip, and prove —
    # via the auditor's compiled-HLO census under enforce — that every
    # cross-chip reduction is an in-graph collective (the host never
    # launches one).
    import warnings as _warnings

    from jax.sharding import Mesh as _SMesh
    from paddle_tpu.serving.arena import StateArena  # noqa: F401 (import gate)

    if jax.device_count() >= 2:
        ms_mesh = _SMesh(np.array(jax.devices()[:2]).reshape(2), ("mp",))

        # unsharded dispatch-count reference over a warm steady window
        ms_ref_eng = pq_engine()
        pq_run(ms_ref_eng)                       # warm
        ms_ref_before = counters.snapshot()
        pq_run(ms_ref_eng)
        ms_ref = counters.delta(ms_ref_before)

        ms_eng = pq_engine(mesh=ms_mesh)
        ms_greedy = pq_run(ms_eng)               # traces the [mp2] programs
        ms_sampled = pq_run(ms_eng, sampled=True)
        if ms_greedy != base_greedy:
            violations["meshserve:greedy_identity"] = (ms_greedy, base_greedy)
        if ms_sampled != base_sampled:
            violations["meshserve:sampled_identity"] = (ms_sampled,
                                                        base_sampled)
        if counters.get("serving.mesh.spec_degraded"):
            violations["meshserve:spec_degraded"] = (
                counters.get("serving.mesh.spec_degraded"), 0)
        # sharded-shard-shape proof on the KV pool: nh/mp heads per chip
        ms_shard = ms_eng.arena.shard_shape("pool_k")
        ms_want = (scfg.num_layers, ms_eng.n_blocks, 4,
                   scfg.num_heads // 2,
                   scfg.hidden_size // scfg.num_heads)
        if ms_shard != ms_want:
            violations["meshserve:kv_shard_shape"] = (ms_shard, ms_want)
        # warm steady window: zero retraces/hydrates/syncs, no arena
        # misses or rebuilds, zero host-launched collectives
        ms_before = counters.snapshot()
        pq_run(ms_eng)
        mssteady = counters.delta(ms_before)
        for k in ("serving.retraces", "jit.traces", "jit.hydrates",
                  "jit.syncs", "serving.arena.program_misses",
                  "serving.arena.program_rebuilds",
                  "dist.collective_launches"):
            if mssteady.get(k, 0):
                violations[f"meshserve:{k}"] = (mssteady.get(k, 0), 0)
        # dispatch economics unchanged vs the unsharded twin
        for k in ("serving.decode_steps", "serving.kv.prefill_chunks",
                  "serving.prefill_batches"):
            if mssteady.get(k, 0) != ms_ref.get(k, 0):
                violations[f"meshserve:dispatch:{k}"] = (mssteady.get(k, 0),
                                                         ms_ref.get(k, 0))
        # in-graph-collectives-only proof: a fresh mesh engine under
        # enforce must audit clean, with the allowlisted census > 0
        from paddle_tpu.analysis import program_audit as _msaudit
        _msaudit.reset_audited()
        pflags.set_flags({"FLAGS_program_audit": "enforce"})
        try:
            msa_before = counters.snapshot()
            with _warnings.catch_warnings():
                _warnings.simplefilter("ignore")
                msa_eng = pq_engine(mesh=ms_mesh)
                msa_tokens = pq_run(msa_eng)
            msa_delta = counters.delta(msa_before)
        finally:
            pflags.set_flags({"FLAGS_program_audit": "off"})
            _msaudit.reset_audited()
        if msa_tokens != base_greedy:
            violations["meshserve:audited_identity"] = (msa_tokens,
                                                        base_greedy)
        if msa_delta.get("analysis.collectives_in_graph", 0) < 1:
            violations["meshserve:collectives_in_graph"] = (
                msa_delta.get("analysis.collectives_in_graph", 0), ">=1")
        if msa_delta.get("analysis.findings", 0):
            violations["meshserve:audit_findings"] = (
                msa_delta.get("analysis.findings", 0), 0)
        mssteady["analysis.collectives_in_graph"] = msa_delta.get(
            "analysis.collectives_in_graph", 0)
    else:
        mssteady = {"skipped":
                    f"needs 2 devices, have {jax.device_count()}"}

    # ---- adapters gate: multi-tenant LoRA serving.  Adapter ids are
    # OPERANDS, so one compiled program serves any tenant mix: the
    # heterogeneous batch below (base + three tenants in the same decode
    # step) must match per-tenant sequential runs token for token, hold
    # the zero-retrace steady economics with dispatch counts equal to
    # the adapter-free twin, and survive an eviction-then-reuse cycle
    # with loads moving but programs never retracing.
    from paddle_tpu.serving.adapters import random_lora_factors as _alf

    ad_tenants = ("acme", "bravo", "coyote")
    ad_factors = {t: _alf(scfg, 3, seed=10 + i, scale=1.0)
                  for i, t in enumerate(ad_tenants)}
    ad_prompts = [rng.randint(0, 64, size=n).tolist()
                  for n in (5, 9, 5, 9)]
    ad_mix = (None, "acme", "bravo", "coyote")

    def ad_engine(slots=5, **kw):
        if slots:
            kw.update(adapter_slots=slots, adapter_rank=4)
        return LLMEngine(smodel, max_slots=4, max_seq_len=32,
                         min_bucket=4, block_size=4,
                         prefill_chunk=8, **kw)

    def ad_run(eng_, mix=ad_mix):
        hs = [eng_.add_request(p, max_new_tokens=3, seed=21 + i,
                               adapter=t)
              for i, (p, t) in enumerate(zip(ad_prompts, mix))]
        while not all(h.is_finished for h in hs):
            eng_.step()
        return [list(h.tokens) for h in hs]

    # adapter-free twin over the identical workload: the dispatch
    # economics reference AND the per-row base tokens
    ad_ref_eng = ad_engine(slots=0)
    ad_ref_tokens = ad_run(ad_ref_eng, mix=(None,) * 4)     # warm
    ad_ref_before = counters.snapshot()
    if ad_run(ad_ref_eng, mix=(None,) * 4) != ad_ref_tokens:
        violations["adapters:ref_determinism"] = ("drift", ad_ref_tokens)
    ad_ref = counters.delta(ad_ref_before)

    ad_eng = ad_engine()
    for t in ad_tenants:
        ad_eng.register_adapter(t, ad_factors[t])
    ad_mixed = ad_run(ad_eng)      # warm: traces +lora programs, cold loads
    # base row bitwise passthrough; every tenant row diverges from base
    if ad_mixed[0] != ad_ref_tokens[0]:
        violations["adapters:base_passthrough"] = (ad_mixed[0],
                                                   ad_ref_tokens[0])
    for i, t in enumerate(ad_mix[1:], start=1):
        if ad_mixed[i] == ad_ref_tokens[i]:
            violations[f"adapters:inert:{t}"] = (ad_mixed[i],
                                                 "!= base tokens")
    # base-ONLY traffic through the adapter engine: adapter-free twin
    # row for row (slot 0 selects the un-adapted activations themselves)
    if ad_run(ad_eng, mix=(None,) * 4) != ad_ref_tokens:
        violations["adapters:base_only_identity"] = ("drift",
                                                     ad_ref_tokens)
    # heterogeneous batch == per-tenant sequential on a fresh engine
    ad_seq_eng = ad_engine()
    for t in ad_tenants:
        ad_seq_eng.register_adapter(t, ad_factors[t])
    for i, t in enumerate(ad_mix[1:], start=1):
        h_ = ad_seq_eng.add_request(ad_prompts[i], max_new_tokens=3,
                                    seed=21 + i, adapter=t)
        while not h_.is_finished:
            ad_seq_eng.step()
        if list(h_.tokens) != ad_mixed[i]:
            violations[f"adapters:sequential:{t}"] = (list(h_.tokens),
                                                      ad_mixed[i])
    # warm steady window: ONE program economy — zero retraces/hydrates/
    # syncs/arena misses, zero adapter loads (all tenants resident),
    # dispatch counts equal to the adapter-free twin
    ad_before = counters.snapshot()
    if ad_run(ad_eng) != ad_mixed:
        violations["adapters:determinism"] = ("drift", ad_mixed)
    adsteady = counters.delta(ad_before)
    for k in ("serving.retraces", "jit.traces", "jit.hydrates",
              "jit.syncs", "serving.arena.program_misses",
              "serving.arena.program_rebuilds", "serving.adapter.loads",
              "serving.adapter.evictions"):
        if adsteady.get(k, 0):
            violations[f"adapters:{k}"] = (adsteady.get(k, 0), 0)
    for k in ("serving.decode_steps", "serving.kv.prefill_chunks",
              "serving.prefill_batches"):
        if adsteady.get(k, 0) != ad_ref.get(k, 0):
            violations[f"adapters:dispatch:{k}"] = (adsteady.get(k, 0),
                                                    ad_ref.get(k, 0))
    # eviction-then-reuse: three MORE tenants through the 5-slot arena
    # force at least one LRU eviction; reloading the original mix pages
    # the evicted tenant back in — loads move, programs never retrace,
    # tokens never change
    for j, t in enumerate(("dingo", "echo", "foxtrot")):
        ad_eng.register_adapter(t, _alf(scfg, 3, seed=40 + j, scale=1.0))
    ad_run(ad_eng, mix=(None, "dingo", "echo", "foxtrot"))
    ad_stats = ad_eng.stats()["adapters"]
    if ad_stats["evictions"] < 1:
        violations["adapters:evictions"] = (ad_stats["evictions"], ">=1")
    ad_re_before = counters.snapshot()
    if ad_run(ad_eng) != ad_mixed:
        violations["adapters:reuse_identity"] = ("drift", ad_mixed)
    ad_reuse = counters.delta(ad_re_before)
    if ad_reuse.get("serving.adapter.loads", 0) < 1:
        violations["adapters:reuse_loads"] = (
            ad_reuse.get("serving.adapter.loads", 0), ">=1")
    for k in ("serving.retraces", "jit.traces"):
        if ad_reuse.get(k, 0):
            violations[f"adapters:reuse:{k}"] = (ad_reuse.get(k, 0), 0)

    # ---- recurrent-state gate: a second kind of cache, no retrace --------
    # A model with recurrent layers keeps one row of state per slot next
    # to the block pool (models/olmo_hybrid.py).  The state arrays are
    # donated through the same chunk and decode programs, so the measure
    # window must trace nothing, and a request served alone in a slot
    # that another request has just left must read what the model's own
    # forward pass puts first (nothing of the last owner's state is left).
    # Last of the gates: its first steps compile, and their gaps must not
    # reach the health gate's windows over the process-wide histograms.
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                               OlmoHybridForCausalLM)
    hmodel = OlmoHybridForCausalLM(OlmoHybridConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_layers=4,
        num_heads=2, linear_num_heads=2, linear_key_head_dim=8,
        linear_value_head_dim=16, max_seq_len=64))
    hmodel.eval()
    heng = LLMEngine(hmodel, max_slots=1, max_seq_len=32, min_bucket=4,
                     block_size=4, prefill_chunk=8)
    pserve(heng, SERVE_LENS_WARM)
    hbefore = counters.snapshot()
    hhs = pserve(heng, SERVE_LENS_MEASURE)
    hsteady = counters.delta(hbefore)
    for k in ("serving.retraces", "jit.traces"):
        if hsteady.get(k, 0):
            violations[f"recurrent:{k}"] = (hsteady.get(k, 0), 0)
    hst = heng.stats()
    if not (hst["state_bytes"] > 0 and hst["prefix_cache"] is False):
        violations["recurrent:stats"] = (
            (hst["state_bytes"], hst["prefix_cache"]), "(>0, False)")
    for h in hhs:
        ids = np.concatenate([h.prompt, np.asarray(h.tokens[:-1], np.int32)])
        lg = np.asarray(hmodel.forward_logits(
            hmodel.decode_state(), ids[None]))[0][len(h.prompt) - 1:]
        if lg.argmax(-1).tolist() != h.tokens:
            violations[f"recurrent:identity@{h.rid}"] = (
                h.tokens, lg.argmax(-1).tolist())

    # ---- latent-cache / expert gate: carried counts, no retrace ----------
    # A model with latent attention and routed experts (models/
    # deepseek_v2.py) caches one row per token with no head axis and has
    # its programs carry the expert layers' load counts on the device.
    # The measure window must trace nothing and register no
    # ``serving.moe.*`` name (a decode launch keeps its one read-back);
    # ``model.moe_load(engine.step_state())`` then publishes exactly the
    # window's tokens, every (token, layer) pair's ``num_experts_per_tok``
    # choices with all the experts held, and the served tokens are the
    # model's own forward pass's.  No earlier engine may have registered
    # those names.  Which body the grouped products took is counted where
    # the programs are traced (``kernels.moe.grouped_mm.pallas`` /
    # ``.xla``, one a traced call): here, with no TPU, every one is the
    # ``ragged_dot`` twin, and the measure window traces none.
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                               DeepseekV2ForCausalLM)
    if any(k.startswith("serving.moe.") for k in counters.snapshot()):
        violations["moe:registered_without_experts"] = (True, False)
    dmodel = DeepseekV2ForCausalLM(DeepseekV2Config(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_layers=3, num_heads=2, q_lora_rank=16,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, n_shared_experts=1, n_routed_experts=8, n_group=4,
        topk_group=2, num_experts_per_tok=2, max_seq_len=64))
    dmodel.eval()
    deng = LLMEngine(dmodel, max_slots=1, max_seq_len=32, min_bucket=4,
                     block_size=4, prefill_chunk=8)
    dtraced = counters.snapshot()
    pserve(deng, SERVE_LENS_WARM)
    dmodel.moe_load(deng.step_state())
    dbefore = counters.snapshot()
    dwarm = counters.delta(dtraced, dbefore)
    if not (dwarm.get("kernels.moe.grouped_mm.xla", 0) > 0
            and dwarm.get("kernels.moe.grouped_mm.pallas", 0) == 0):
        violations["moe:grouped_mm_body"] = (
            (dwarm.get("kernels.moe.grouped_mm.xla", 0),
             dwarm.get("kernels.moe.grouped_mm.pallas", 0)), "(>0, 0)")
    dhs = pserve(deng, SERVE_LENS_MEASURE)
    dsteady_moe = counters.delta(dbefore)
    for k in ("serving.retraces", "jit.traces", "serving.moe.tokens",
              "serving.moe.assignments", "kernels.moe.grouped_mm.xla",
              "kernels.moe.grouped_mm.pallas"):
        if dsteady_moe.get(k, 0):
            violations[f"moe:{k}"] = (dsteady_moe.get(k, 0), 0)
    dload = dmodel.moe_load(deng.step_state())
    dmoved = counters.delta(dbefore)
    dtokens = sum(len(h.prompt) + len(h.tokens) - 1 for h in dhs)
    want_moe = {"serving.moe.tokens": dtokens,
                "serving.moe.assignments": dtokens * 2 * 2}
    for k, want in want_moe.items():
        if dmoved.get(k, 0) != want:
            violations[f"moe:published:{k}"] = (dmoved.get(k, 0), want)
    dst = deng.stats()
    if not (dst["prefix_cache"] is False and deng._pv is None
            and dload["load_max_over_mean"] >= 1.0):
        violations["moe:stats"] = (
            (dst["prefix_cache"], dload["load_max_over_mean"]),
            "(False, >= 1)")
    for h in dhs:
        ids = np.concatenate([h.prompt, np.asarray(h.tokens[:-1], np.int32)])
        lg = np.asarray(dmodel.forward_logits(
            dmodel.decode_state(), ids[None]))[0][len(h.prompt) - 1:]
        if lg.argmax(-1).tolist() != h.tokens:
            violations[f"moe:identity@{h.rid}"] = (
                h.tokens, lg.argmax(-1).tolist())

    # ---- block-decode gate: one read-back, the diffusion records ---------
    # A model that generates by diffusion over blocks (models/sdar.py) is
    # served by serving/block_decode.py: a decode launch is one pass of
    # every running row's block, a row yields its tokens when its block
    # commits.  The measure window must trace nothing; its records count a
    # launch (``serving.decode_steps``), the rows it passed
    # (``serving.diffusion.row_passes``), the blocks committed, the
    # positions revealed, and the tokens EMITTED
    # (``serving.decode_tokens``); no earlier engine may have registered a
    # ``serving.diffusion.*`` name.
    from paddle_tpu.models.sdar import SdarConfig, SdarMoeForCausalLM
    if any(k.startswith("serving.diffusion.") for k in counters.snapshot()):
        violations["diffusion:registered_without_blocks"] = (True, False)
    bmodel = SdarMoeForCausalLM(SdarConfig(
        vocab_size=64, hidden_size=32, moe_intermediate_size=16,
        num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
        num_experts=8, num_experts_per_tok=2, max_seq_len=64,
        mask_token_id=63))
    bmodel.eval()
    beng = LLMEngine(bmodel, max_slots=1, max_seq_len=32, min_bucket=4,
                     block_size=4, prefill_chunk=8)
    pserve(beng, SERVE_LENS_WARM)
    bbefore = counters.snapshot()
    bhs = pserve(beng, SERVE_LENS_MEASURE)
    bsteady = counters.delta(bbefore)
    # prompts of 4 and 5 tokens, 3 new tokens each: a block of 4 masked
    # positions (4 passes and the commit) and one of 3 (3 and the commit)
    want_blocks = {"serving.retraces": 0, "jit.traces": 0,
                   "serving.decode_steps": 9,
                   "serving.diffusion.row_passes": 9,
                   "serving.diffusion.commits": 2,
                   "serving.diffusion.revealed": 7,
                   "serving.decode_tokens": 6}
    for k, want in want_blocks.items():
        if bsteady.get(k, 0) != want:
            violations[f"diffusion:{k}"] = (bsteady.get(k, 0), want)
    if not (beng.stats()["prefix_cache"] is False and beng._pv is None
            and all(len(h.tokens) == 3 for h in bhs)):
        violations["diffusion:stats"] = (
            (beng.stats()["prefix_cache"], [len(h.tokens) for h in bhs]),
            "(False, [3, 3])")

    # ---- window gate: a ring of window blocks a row -----------------------
    # A model with window layers (models/trinity.py) keeps their K/V in a
    # second pool, at most ``window_entries`` blocks a row reused as a ring
    # (``serving.kv.window_blocks_recycled`` counts the entries taken over
    # as rows pass the window); the measure window must trace nothing, no
    # earlier engine may have registered a ``serving.kv.window_*`` name,
    # and both pools must be whole again once the requests finish.  Which
    # form a chunk's attention took is counted where the chunk programs
    # are traced (``kernels.window_attention.prefill.pallas`` / ``.xla``,
    # one a traced call): here, with no TPU, every one is the XLA twin,
    # and the measure window traces none; an engine under the interpret
    # hook traces the Pallas kernel alone and serves the twin's tokens.
    from paddle_tpu.models.trinity import TrinityConfig, TrinityForCausalLM
    if any(k.startswith("serving.kv.window_") for k in counters.snapshot()):
        violations["window:registered_without_window"] = (True, False)
    wmodel = TrinityForCausalLM(TrinityConfig(
        vocab_size=64, hidden_size=32, intermediate_size=32,
        moe_intermediate_size=16, num_layers=5, num_dense_layers=1,
        num_heads=4, num_kv_heads=2, head_dim=8, sliding_window=8,
        num_experts=8, num_experts_per_tok=2, max_seq_len=64))
    wmodel.eval()
    weng = LLMEngine(wmodel, max_slots=2, max_seq_len=32, min_bucket=4,
                     block_size=4, prefill_chunk=8)
    wtraced = counters.snapshot()
    pserve(weng, (21, 25))
    wbefore = counters.snapshot()
    wwarm = counters.delta(wtraced, wbefore)
    if not (wwarm.get("kernels.window_attention.prefill.xla", 0) > 0
            and wwarm.get("kernels.window_attention.prefill.pallas", 0) == 0):
        violations["window:prefill_attn_body"] = (
            (wwarm.get("kernels.window_attention.prefill.xla", 0),
             wwarm.get("kernels.window_attention.prefill.pallas", 0)),
            "(>0, 0)")
    pserve(weng, (22, 26))
    wsteady = counters.delta(wbefore)
    # a ring of ceil((8 + 8) / 4) + 1 = 5 entries; the rows write up to
    # positions 23 and 27 (3 new tokens): blocks 0-5 and 0-6, three
    # entries taken over
    want_window = {"serving.retraces": 0, "jit.traces": 0,
                   "serving.kv.window_blocks_recycled": 3,
                   "kernels.window_attention.prefill.xla": 0,
                   "kernels.window_attention.prefill.pallas": 0}
    for k, want in want_window.items():
        if wsteady.get(k, 0) != want:
            violations[f"window:{k}"] = (wsteady.get(k, 0), want)
    wst = weng.stats()
    if not (wst["prefix_cache"] is False and weng.window_entries == 5
            and wst["window_blocks_live"] == 0
            and wst["blocks_free"] == wst["blocks_total"]):
        violations["window:stats"] = (
            (wst["prefix_cache"], weng.window_entries,
             wst["window_blocks_live"], wst["blocks_free"]),
            f"(False, 5, 0, {wst['blocks_total']})")

    def wserve(eng_):
        hs = [eng_.add_request(p, max_new_tokens=3)
              for p in (list(range(3, 24)), list(range(60, 35, -1)))]
        while not all(h.is_finished for h in hs):
            eng_.step()
        return [list(h.tokens) for h in hs]

    wtwin = wserve(weng)
    _pa._INTERPRET[0] = True
    try:
        wkbefore = counters.snapshot()
        wkernel = wserve(LLMEngine(wmodel, max_slots=2, max_seq_len=32,
                                   min_bucket=4, block_size=4,
                                   prefill_chunk=8))
        wkwarm = counters.delta(wkbefore)
    finally:
        _pa._INTERPRET[0] = False
    if not (wkwarm.get("kernels.window_attention.prefill.pallas", 0) > 0
            and wkwarm.get("kernels.window_attention.prefill.xla", 0) == 0):
        violations["window:prefill_attn_kernel"] = (
            (wkwarm.get("kernels.window_attention.prefill.xla", 0),
             wkwarm.get("kernels.window_attention.prefill.pallas", 0)),
            "(0, >0)")
    if wkernel != wtwin:
        violations["window:prefill_attn_kernel_tokens"] = (wkernel, wtwin)

    # ---- selective-scan gate: a Mamba layer's state a slot ---------------
    # A model with Mamba layers (models/jamba.py) scans each layer's
    # diagonal state per slot; which form the scan took is counted where
    # programs are traced (``kernels.selective_scan.pallas`` / ``.xla``,
    # one a traced call): here, with no TPU, every one is the XLA twin,
    # the measure window traces none, and an engine under the interpret
    # hook traces the kernel alone and serves the twin's tokens.
    from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM
    smodel = JambaForCausalLM(JambaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=32, num_layers=3,
        num_heads=2, num_kv_heads=1, attn_layer_offset=1,
        attn_layer_period=3, dt_rank=4, max_seq_len=64))
    smodel.eval()

    def sserve(eng_):
        hs = [eng_.add_request(p, max_new_tokens=3)
              for p in (list(range(3, 24)), list(range(60, 35, -1)))]
        while not all(h.is_finished for h in hs):
            eng_.step()
        return [list(h.tokens) for h in hs]

    def sengine():
        return LLMEngine(smodel, max_slots=2, max_seq_len=32, min_bucket=4,
                         block_size=4, prefill_chunk=8)

    straced = counters.snapshot()
    seng = sengine()
    stwin = sserve(seng)
    swarm = counters.delta(straced)
    sbefore = counters.snapshot()
    sserve(seng)
    ssteady = counters.delta(sbefore)
    if not (swarm.get("kernels.selective_scan.xla", 0) > 0
            and swarm.get("kernels.selective_scan.pallas", 0) == 0):
        violations["scan:twin"] = (
            (swarm.get("kernels.selective_scan.xla", 0),
             swarm.get("kernels.selective_scan.pallas", 0)), "(>0, 0)")
    for k in ("serving.retraces", "jit.traces", "kernels.selective_scan.xla",
              "kernels.selective_scan.pallas"):
        if ssteady.get(k, 0) != 0:
            violations[f"scan:{k}"] = (ssteady.get(k, 0), 0)
    _pa._INTERPRET[0] = True
    try:
        skbefore = counters.snapshot()
        skernel = sserve(sengine())
        skwarm = counters.delta(skbefore)
    finally:
        _pa._INTERPRET[0] = False
    if not (skwarm.get("kernels.selective_scan.pallas", 0) > 0
            and skwarm.get("kernels.selective_scan.xla", 0) == 0):
        violations["scan:kernel"] = (
            (skwarm.get("kernels.selective_scan.xla", 0),
             skwarm.get("kernels.selective_scan.pallas", 0)), "(0, >0)")
    if skernel != stwin:
        violations["scan:kernel_tokens"] = (skernel, stwin)

    result = {"metric": "steady_state_counter_violations",
              "value": len(violations),
              "unit": f"violations/{MEASURE} steps "
                      f"+ {FUSED_MEASURE} fused windows "
                      f"+ {len(SERVE_LENS_MEASURE)} served requests",
              "violations": {k: {"got": got, "want": want}
                             for k, (got, want) in violations.items()},
              "steady_delta": steady,
              "fused_steady_delta": fsteady,
              "mesh_steady_delta": msteady,
              "mesh_fused_delta": fmsteady,
              "paged_steady_delta": psteady,
              "paged_prefill_programs": peng.stats()["prefill_programs"],
              "paged_prefix": {"hits": pc_hits,
                               "chunks_cached": pc_chunks,
                               "chunks_nocache": nc_chunks},
              "paged_pallas_steady_delta": ksteady,
              "paged_quant_steady_delta": qsteady,
              "paged_quant_logit_drift": quant_drift,
              "spec_steady_delta": {k: v for k, v in spsteady.items()
                                    if k.startswith(("serving.spec.",
                                                     "serving.retraces",
                                                     "jit."))},
              "spec_programs": {"draft": spec_dkeys,
                                "verify": spec_vkeys},
              "fleet_steady_delta": flsteady,
              "fleet_churn_delta": {k: v for k, v in chsteady.items()
                                    if k.startswith("serving.fleet.")},
              "disagg_delta": {k: v for k, v in dsteady.items()
                               if k.startswith(("serving.fleet.migrate.",
                                                "serving.retraces"))},
              "tiering_delta": {k: v for k, v in tsteady.items()
                                if k.startswith(("serving.kv.tier.",
                                                 "serving.kv.host_",
                                                 "serving.retraces",
                                                 "jit.traces"))},
              "tiering_chaos": {k: v for k, v in tdrop.items()
                                if k.startswith(
                                    ("serving.kv.tier.",
                                     "resilience.faults_injected"))},
              "ckpt_steady_delta": {k: v for k, v in csteady.items()
                                    if k.startswith(("jit.", "resilience."))},
              "fault_delta": {k: v for k, v in rsteady.items()
                              if k.startswith("resilience.")},
              "metrics_parity": metrics_parity,
              "trace_parity": {"off": _pick(toff), "on": _pick(ton),
                               "off_trace_moved": off_moved,
                               "on_finished": ton.get("trace.finished", 0)},
              "trace_span_ratios": trace_ratios,
              "health_parity": {"off": _pick(hoff), "on": _pick(hon),
                                "off_health_moved": hoff_moved,
                                "on_ticks": hon.get("health.ticks", 0),
                                "clean_fired": hclean_fired},
              "health_chaos": {"slow_decode_fired": hfired,
                               "slow_decode_dump": list(hdump),
                               "kv_fired": kfired,
                               "kv_dump": list(kdump),
                               "admission_level": hadm,
                               "ops": ops_live},
              "program_audit": {"off": audit_off, "on": audit_on,
                                "audits": audits_run,
                                "findings": audit_delta.get(
                                    "analysis.findings", 0),
                                "fixtures": fixture_got},
              "meshserve_delta": {k: v for k, v in mssteady.items()
                                  if not k.endswith("_ns")},
              "adapters_delta": {
                  "steady": {k: v for k, v in adsteady.items()
                             if k.startswith(("serving.adapter.",
                                              "serving.retraces",
                                              "jit.traces"))},
                  "reuse": {k: v for k, v in ad_reuse.items()
                            if k.startswith(("serving.adapter.",
                                             "serving.retraces",
                                             "jit.traces"))},
                  "evictions": ad_stats["evictions"],
                  "resident": ad_stats["resident"]},
              "devicetime": {"off": _pick(dt_off), "on": _pick(dt_on),
                             "off_moved": dt_off_moved,
                             "dispatches": dt_disp,
                             "sampled_syncs": dt_syncs,
                             "ledger_programs": len(dt_snap["programs"]),
                             "mfu_rows": dt_mfu_rows[:4],
                             "attribution_dominant": dt_dominant}}
    print(json.dumps(result))
    if violations:
        raise AssertionError(
            "steady-state counter invariants violated (got != want): "
            + ", ".join(f"{k}: {got} != {want}"
                        for k, (got, want) in sorted(violations.items())))
    return result


if __name__ == "__main__":
    run()
