"""Perf-contract smoke: 3 steps of a tiny GPT on CPU.

Steps 2-3 (steady state) must do ZERO host-side hydrate/bind work — the
device-resident contract of jit.CompiledTrainStep, watched through the
process-global ``paddle_tpu.profiler.counters`` registry (jit.host.* keys;
``jit.host_sync_counts()`` is now a view over the same counters).  Step 3
must additionally be a pure cache hit: zero retraces (``jit.traces``).
Prints one JSON line; raises on violation.

A fused-dispatch phase re-runs the same model with ``fused_steps=K`` and
gates the launch economics: a steady K-step window must be exactly ONE
XLA dispatch (``jit.host.dispatches == jit.steps / K``) with zero
retraces.

A checkpointed-run phase gates the resilience contract: async
``resilience.CheckpointManager`` saves interleaved with fused windows
must cost exactly ONE counter-gated ``jit.syncs`` (+ its
``bind_layer_state``/``bind_optimizer_state`` pair) per save and nothing
else — zero retraces, zero rehydrates, zero ``layer_state``/
``optimizer_state`` host reads; the disk write overlaps the next window
on a background thread.

A flight-recorder phase injects a ``nan_loss`` fault into a tiny
``FaultTolerantTrainer`` run and gates the postmortem contract: recovery
must leave exactly one flight dump (reason ``trainer_recover``) whose
context names the ``NonFiniteLossError``, while the run itself still
finishes with finite losses.

A goodput phase runs a tiny ``FaultTolerantTrainer`` twice — clean, and
under an injected preemption — and gates the wall-clock ledger
(``profiler.goodput``): in both runs >=99% of wall time must land in a
named bucket, and the preempted run must actually fill the ``recovery``
and ``restore_replay`` badput buckets.

A serving phase runs mixed-length staggered requests through
``serving.LLMEngine`` and asserts the outputs are TOKEN-IDENTICAL to
sequential per-request ``GPT.generate``; it reports decode tokens/s for
both paths (the speedup is informational on CPU — the batching win is a
TPU property).

A mesh phase (on >=2 devices — forced host devices under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) re-runs the same
fused GPT mesh-native on a dp=2 mesh (``CompiledTrainStep(mesh=...)``,
batch staged with data-parallel ``NamedSharding``) and gates the
multi-chip economics: a steady fused window is still exactly ONE XLA
dispatch with zero retraces, and the losses match the single-device
fused run (GSPMD gradient averaging is numerically invisible).

Run directly (``python scripts/bench_smoke.py``), via ``PTPU_BENCH_SMOKE=1
python bench.py``, or through tests/test_train_step_state.py (tier-1).
"""

import json
import os


def run():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the mesh phase needs >1 device; only effective before the first jax
    # import, no-op on real TPUs
    if ("--xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.jit as pjit
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    from paddle_tpu.profiler import counters

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                    max_seq_len=64, use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    ids = paddle.randint(0, cfg.vocab_size, [2, 64])
    labels = paddle.randint(0, cfg.vocab_size, [2, 64])

    def loss_fn(m, x, l):
        return crit(m(x), l)

    step = pjit.CompiledTrainStep(model, loss_fn, opt)
    losses = [float(step(ids, labels).numpy())]  # step 1: hydrate + compile
    before = counters.snapshot()
    losses.append(float(step(ids, labels).numpy()))  # step 2 (retrace only)
    mid = counters.snapshot()
    losses.append(float(step(ids, labels).numpy()))  # step 3 (cached)
    after = counters.snapshot()

    host_keys = ["jit.host." + k for k in pjit._HOST_SYNC_KEYS]
    host_keys += ["jit.hydrates", "jit.syncs"]
    steady = counters.delta(before, after)
    host_delta = {k: steady.get(k, 0) for k in host_keys}
    step3 = counters.delta(mid, after)

    # ---- fused multi-step dispatch: one launch per K-step window --------
    from paddle_tpu.io import Window
    fused_k = 2
    paddle.seed(0)
    fmodel = GPTForCausalLM(cfg)
    fopt = paddle.optimizer.AdamW(1e-4, parameters=fmodel.parameters())
    fstep = pjit.CompiledTrainStep(fmodel, loss_fn, fopt,
                                   fused_steps=fused_k)
    wids = paddle.to_tensor(np.stack([np.asarray(ids.numpy())] * fused_k))
    wlabels = paddle.to_tensor(np.stack([np.asarray(labels.numpy())]
                                        * fused_k))
    win = Window((wids, wlabels), fused_k)
    fstep(win).numpy()   # window 1: priming single-step fallback
    fstep(win).numpy()   # window 2: scan compile
    fbefore = counters.snapshot()
    flosses = [round(float(l), 6)
               for l in np.asarray(fstep(win).numpy())]  # steady window
    fused = counters.delta(fbefore)
    fused_dispatches = fused.get("jit.host.dispatches", 0)
    fused_steps_done = fused.get("jit.steps", 0)

    # ---- resilience: async checkpoints overlap the next fused window ----
    import tempfile
    import time
    from paddle_tpu.resilience import CheckpointManager

    ckpt_saves = 2
    with tempfile.TemporaryDirectory() as ckdir:
        mgr = CheckpointManager(ckdir, keep_last=2, async_save=True)
        rbefore = counters.snapshot()
        t0 = time.perf_counter()
        for i in range(ckpt_saves):
            # snapshot (one sync + D2H copies) on this thread, disk write
            # on a daemon thread — the next fused window overlaps it
            mgr.save(fstep, (i + 1) * fused_k, blocking=False)
            fstep(win).numpy()
        mgr.wait()
        ckpt_wall_s = time.perf_counter() - t0
        rdelta = counters.delta(rbefore)
    ckpt_host_delta = {k: rdelta.get(k, 0) for k in host_keys}
    # budget: exactly one counter-gated sync (one bind pair) per save
    ckpt_extra_syncs = (
        sum(ckpt_host_delta.values())
        - rdelta.get("jit.syncs", 0)
        - rdelta.get("jit.host.bind_layer_state", 0)
        - rdelta.get("jit.host.bind_optimizer_state", 0))

    # ---- flight recorder: an injected NaN fault must leave a postmortem -
    import paddle_tpu.nn as nn
    from paddle_tpu.io import DataLoader, TensorDataset
    from paddle_tpu.profiler import flight
    from paddle_tpu.resilience import (CheckpointManager as _CkptMgr,
                                       FaultTolerantTrainer, faultinject)

    def _mse(m, x, y):
        return ((m(x) - y) ** 2).mean()

    paddle.seed(0)
    fnet = nn.Sequential(nn.Linear(6, 12), nn.GELU(), nn.Linear(12, 3))
    fr_opt = paddle.optimizer.AdamW(5e-2, parameters=fnet.parameters())
    fr_step = pjit.CompiledTrainStep(fnet, _mse, fr_opt)
    frng = np.random.RandomState(3)
    fr_ds = TensorDataset(
        [paddle.to_tensor(frng.randn(32, 6).astype("float32")),
         paddle.to_tensor(frng.randn(32, 3).astype("float32"))])
    with tempfile.TemporaryDirectory() as fdir:
        flight.configure(directory=fdir)
        flight.clear()
        with faultinject.fault_schedule("nan_loss@3"):
            trainer = FaultTolerantTrainer(
                fr_step, lambda epoch: DataLoader(fr_ds, batch_size=4,
                                                  shuffle=False),
                _CkptMgr(os.path.join(fdir, "ckpt"), keep_last=2),
                epochs=1, max_steps=6, save_every=2)
            fr_losses = trainer.run()
        fr_dump_path = flight.last_dump_path()
        fr_bundle = flight.load(fr_dump_path) if fr_dump_path else {}
        flight.configure(directory="")
    flight_phase = {
        "flight_nan_recoveries": trainer.recoveries,
        "flight_dump_reason": fr_bundle.get("reason"),
        "flight_dump_error": (fr_bundle.get("context") or {}).get("error"),
        "flight_dump_events": len(fr_bundle.get("events", [])),
    }

    # ---- goodput ledger: >=99% of trainer wall time lands in a named
    # bucket, on a clean run AND under an injected preemption (where the
    # recovery / restore_replay buckets must actually fill) -------------
    def _goodput_run(schedule=None):
        paddle.seed(0)
        gnet = nn.Sequential(nn.Linear(6, 12), nn.GELU(), nn.Linear(12, 3))
        g_opt = paddle.optimizer.AdamW(5e-2, parameters=gnet.parameters())
        g_step = pjit.CompiledTrainStep(gnet, _mse, g_opt)
        with tempfile.TemporaryDirectory() as gdir:
            gtrainer = FaultTolerantTrainer(
                g_step, lambda epoch: DataLoader(fr_ds, batch_size=4,
                                                 shuffle=False),
                _CkptMgr(os.path.join(gdir, "ckpt"), keep_last=2),
                epochs=1, max_steps=6, save_every=2)
            if schedule:
                with faultinject.fault_schedule(schedule):
                    gtrainer.run()
            else:
                gtrainer.run()
        return gtrainer.goodput.report()

    g_clean = _goodput_run()
    g_fault = _goodput_run("preempt@3")
    goodput_phase = {
        "goodput_clean_accounted": round(g_clean["accounted"], 4),
        "goodput_clean_fraction": round(g_clean["goodput"], 4),
        "goodput_fault_accounted": round(g_fault["accounted"], 4),
        "goodput_fault_fraction": round(g_fault["goodput"], 4),
        "goodput_fault_recovery_s":
            round(g_fault["buckets_s"].get("recovery", 0.0), 4),
        "goodput_fault_restore_s":
            round(g_fault["buckets_s"].get("restore_replay", 0.0), 4),
    }

    # ---- serving: engine output must match sequential generate ----------
    from paddle_tpu.serving import LLMEngine

    paddle.seed(0)
    smodel = GPTForCausalLM(cfg)
    smodel.eval()
    rng = np.random.RandomState(11)
    max_new = 8
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in (5, 9, 3, 12, 7, 6, 10, 4)]

    # sequential baseline: one generate call per request (warm pass first
    # so both paths are timed compiled)
    def seq_pass():
        return [np.asarray(smodel.generate(
            paddle.to_tensor(np.asarray([p])),
            max_new_tokens=max_new).numpy())[0] for p in prompts]
    seq_pass()
    t0 = time.perf_counter()
    seq_outs = seq_pass()
    seq_s = time.perf_counter() - t0

    decode_tokens = len(prompts) * max_new
    seq_tps = decode_tokens / max(seq_s, 1e-9)

    # same prompts, same tokens, zero steady retraces
    peng = LLMEngine(smodel, max_slots=4, max_seq_len=cfg.max_seq_len,
                     min_bucket=4, block_size=4, prefill_chunk=8)
    # two warm passes: the first compiles the chunk/decode programs, the
    # second re-serves the (now prefix-cached) prompts so the timed pass
    # runs the same prefix-hit chunk pattern against warm programs
    for _ in range(2):
        for o in peng.generate(prompts, max_new_tokens=max_new):
            pass
    pbefore = counters.snapshot()
    t0 = time.perf_counter()
    paged_outs = peng.generate(prompts, max_new_tokens=max_new)
    paged_s = time.perf_counter() - t0
    pdelta = counters.delta(pbefore)
    paged_match = all(np.array_equal(e, s)
                      for e, s in zip(paged_outs, seq_outs))
    paged_tps = decode_tokens / max(paged_s, 1e-9)
    # shared-prefix leg: one system prompt, distinct tails, served
    # sequentially so every finish feeds the prefix tree
    sysp = rng.randint(0, cfg.vocab_size, size=12).tolist()
    phbefore = counters.snapshot()
    for _ in range(3):
        tail = rng.randint(0, cfg.vocab_size, size=3).tolist()
        for o in peng.generate([sysp + tail], max_new_tokens=4):
            pass
    phdelta = counters.delta(phbefore)

    # ---- mesh: fused dp=2 SPMD keeps the launch economics + the loss ----
    import jax
    if jax.device_count() >= 2:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1),
                    ("dp", "mp"))
        paddle.seed(0)
        mmodel = GPTForCausalLM(cfg)
        mopt = paddle.optimizer.AdamW(1e-4,
                                      parameters=mmodel.parameters())
        mstep = pjit.CompiledTrainStep(mmodel, loss_fn, mopt,
                                       fused_steps=fused_k, mesh=mesh)
        # stage the window with its data-parallel sharding up front, the
        # way the sharded prefetchers do (batch axis is dim 1 of a window)
        wsh = NamedSharding(mesh, P(None, *mstep._batch_spec))
        mwin = Window(
            tuple(paddle.Tensor(jax.device_put(t._data, wsh))
                  for t in (wids, wlabels)), fused_k)
        mstep(mwin).numpy()   # window 1: priming single-step fallback
        mstep(mwin).numpy()   # window 2: scan compile
        mbefore = counters.snapshot()
        mlosses = [round(float(l), 6)
                   for l in np.asarray(mstep(mwin).numpy())]
        mdelta = counters.delta(mbefore)
        mesh_phase = {
            "mesh_devices": 2,
            "mesh_window_dispatches": mdelta.get("jit.host.dispatches",
                                                 0),
            "mesh_window_steps": mdelta.get("jit.steps", 0),
            "mesh_window_retraces": mdelta.get("jit.traces", 0),
            "mesh_window_rehydrates": mdelta.get("jit.hydrates", 0),
            "mesh_sharded_put_bytes": counters.get(
                "dist.device_put_sharded_bytes", 0),
            "mesh_losses": mlosses,
            "mesh_losses_match": bool(np.allclose(mlosses, flosses,
                                                  rtol=1e-4, atol=1e-5)),
        }
    else:
        mesh_phase = {"mesh_devices": jax.device_count(),
                      "mesh_skipped": "needs 2 devices"}

    result = {"metric": "steady_state_host_syncs",
              "value": sum(host_delta.values()),
              "unit": "calls/2 steps",
              "delta": host_delta,
              "step3_retraces": step3.get("jit.traces", 0),
              "steady_dispatches": steady.get("jit.host.dispatches", 0),
              "counters": {k: v for k, v in steady.items()
                           if k.startswith(("jit.", "io.", "dist.",
                                            "optimizer."))},
              "losses": [round(l, 6) for l in losses],
              "fused_k": fused_k,
              "fused_window_dispatches": fused_dispatches,
              "fused_window_steps": fused_steps_done,
              "fused_window_retraces": fused.get("jit.traces", 0),
              "fused_losses": flosses,
              "ckpt_async_saves": rdelta.get("resilience.saves", 0),
              "ckpt_save_ms": rdelta.get("resilience.save_ms", 0),
              "ckpt_wall_s": round(ckpt_wall_s, 4),
              "ckpt_syncs": rdelta.get("jit.syncs", 0),
              "ckpt_retraces": rdelta.get("jit.traces", 0),
              "ckpt_rehydrates": rdelta.get("jit.hydrates", 0),
              "ckpt_extra_host_syncs": ckpt_extra_syncs,
              "serve_requests": len(prompts),
              "serve_decode_tokens": decode_tokens,
              "sequential_decode_tokens_per_sec": round(seq_tps, 1),
              "paged_outputs_match_generate": paged_match,
              "paged_steady_retraces": pdelta.get("serving.retraces", 0),
              "paged_decode_tokens_per_sec": round(paged_tps, 1),
              "paged_prefix_hits": phdelta.get("serving.kv.prefix_hits", 0),
              "paged_prefill_chunks": phdelta.get("serving.kv.prefill_chunks",
                                                  0),
              "paged_cow_copies": pdelta.get("serving.kv.cow_copies", 0)}
    result.update(flight_phase)
    result.update(goodput_phase)
    result.update(mesh_phase)
    print(json.dumps(result))
    if sum(host_delta.values()) != 0:
        raise AssertionError(
            f"steady-state steps did host hydrate/bind work: {host_delta}")
    if result["step3_retraces"] != 0:
        raise AssertionError(
            f"step 3 retraced: jit.traces += {result['step3_retraces']} "
            "(expected a pure jit cache hit after the step-2 "
            "accumulator-structure retrace)")
    if result["steady_dispatches"] != 2:
        raise AssertionError(
            "steady-state single-step mode must be exactly 1 XLA dispatch "
            f"per step: jit.host.dispatches += {result['steady_dispatches']} "
            "over 2 steps")
    if fused_steps_done != fused_k or fused_dispatches != 1:
        raise AssertionError(
            "fused dispatch economics violated: a steady fused window must "
            f"be jit.steps / K == {fused_steps_done} / {fused_k} == 1 XLA "
            f"dispatch, got jit.host.dispatches += {fused_dispatches}")
    if result["fused_window_retraces"] != 0:
        raise AssertionError(
            "steady fused window retraced: jit.traces += "
            f"{result['fused_window_retraces']}")
    if result["ckpt_async_saves"] != ckpt_saves or \
            rdelta.get("resilience.save_failures", 0) != 0:
        raise AssertionError(
            f"checkpointed run: expected {ckpt_saves} clean async saves, "
            f"got {result['ckpt_async_saves']} (failures: "
            f"{rdelta.get('resilience.save_failures', 0)})")
    if result["ckpt_syncs"] != ckpt_saves or result["ckpt_retraces"] != 0 \
            or result["ckpt_rehydrates"] != 0 or ckpt_extra_syncs != 0:
        raise AssertionError(
            "checkpointed run broke the one-sync-per-save budget: "
            f"jit.syncs += {result['ckpt_syncs']} (want {ckpt_saves}), "
            f"retraces {result['ckpt_retraces']}, rehydrates "
            f"{result['ckpt_rehydrates']}, extra host syncs "
            f"{ckpt_extra_syncs}: {ckpt_host_delta}")
    if not all(np.isfinite(l) for l in losses + flosses):
        raise AssertionError(
            f"non-finite loss in smoke run: {losses} / {flosses}")
    if (trainer.recoveries != 1 or fr_dump_path is None
            or fr_bundle.get("reason") != "trainer_recover"
            or "NonFiniteLossError" not in (flight_phase["flight_dump_error"]
                                            or "")
            or not all(np.isfinite(v) for v in fr_losses.values())):
        raise AssertionError(
            "injected NaN fault did not produce a flight-recorder "
            f"postmortem (or the recovery was unclean): {flight_phase}, "
            f"dump={fr_dump_path}")
    if goodput_phase["goodput_clean_accounted"] < 0.99 or \
            goodput_phase["goodput_fault_accounted"] < 0.99:
        raise AssertionError(
            "goodput ledger failed to account >=99% of trainer wall time: "
            f"clean {goodput_phase['goodput_clean_accounted']}, "
            f"faulted {goodput_phase['goodput_fault_accounted']}")
    if goodput_phase["goodput_fault_recovery_s"] <= 0 or \
            goodput_phase["goodput_fault_restore_s"] <= 0:
        raise AssertionError(
            "preempted run left the recovery / restore_replay goodput "
            f"buckets empty: {goodput_phase}")
    if not result["paged_outputs_match_generate"]:
        raise AssertionError(
            "serving engine output diverged from sequential GPT.generate "
            "on the same prompts (continuous batching, block tables, "
            "prefix sharing and chunked prefill must be invisible in the "
            "tokens)")
    if result["paged_steady_retraces"] != 0:
        raise AssertionError(
            "warm paged pass retraced: serving.retraces += "
            f"{result['paged_steady_retraces']} (block tables are "
            "operands; steady state is chunk buckets + one decode + one "
            "COW program)")
    if result["paged_prefix_hits"] < 2:
        raise AssertionError(
            "shared-prefix workload scored "
            f"{result['paged_prefix_hits']} prefix-cache hits (want >= 2)")
    if "mesh_skipped" not in mesh_phase:
        if (mesh_phase["mesh_window_dispatches"] != 1
                or mesh_phase["mesh_window_steps"] != fused_k
                or mesh_phase["mesh_window_retraces"] != 0
                or mesh_phase["mesh_window_rehydrates"] != 0):
            raise AssertionError(
                "mesh fused-dispatch economics violated: a steady dp=2 "
                f"window must be 1 XLA dispatch / {fused_k} steps with "
                f"zero retraces/rehydrates, got {mesh_phase}")
        if not mesh_phase["mesh_losses_match"]:
            raise AssertionError(
                "mesh dp=2 losses diverged from the single-device fused "
                f"run: {mesh_phase['mesh_losses']} vs {flosses}")
        if mesh_phase["mesh_sharded_put_bytes"] <= 0:
            raise AssertionError(
                "mesh phase staged no sharded bytes — "
                "dist.device_put_sharded_bytes never moved")
    return result


if __name__ == "__main__":
    run()
