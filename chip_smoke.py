#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the main path once on a TPU, at the full width of GPT-760M
(``GPTConfig.gpt3_760m``: hidden 1536, 24 layers, 16 heads of 96, vocab
50304, bf16; random weights from ``--seed``), through the entry points a
user calls, and checks what comes out by the repo's own means.  One
process, stdlib + the package.  It asserts the platform is ``"tpu"`` before
anything else, never sets ``JAX_PLATFORMS`` and has no CPU option: with no
accelerator it exits non-zero and prints no result.

    python chip_smoke.py             one chip: kernels, train, serve
    python chip_smoke.py --chips 4   four chips: the mesh paths and what
                                     they are compared with, nothing else

Default run, three phases; each raises on failure, so the exit code is
non-zero and the last line is never printed:

* ``kernels`` — flash attention fwd+bwd against ``reference_attention``
  and the paged decode kernel (bf16 and int8 pools) against the XLA
  gather twin, each asserted to have lowered to a Mosaic custom call.
* ``train`` — ``CompiledTrainStep`` on GPT-760M as ``bench.py`` builds it
  (flash on, ``recompute="selective_lean"``, AdamW, 8 x 1024): warm-up,
  three steps on one repeated batch (loss finite and lower at the end,
  no retrace), then ``fused_steps=4`` windows.
* ``serve`` — ``LLMEngine`` over a bf16 GPT-3 XL (1.3B:
  16 heads of 128, the width whose K/V slabs are whole tiles, so the
  decode program runs the Pallas block-table walk) with a pool of 8 rows
  x 1024 tokens: 8 greedy requests (prompts of 32-512 tokens, 32 new
  tokens) through ``add_request``/``step`` — with a bf16 pool and with
  ``kv_dtype="int8"``; both must have decoded through the kernel.

``--chips 4`` runs (a) mesh-native ``CompiledTrainStep(mesh=, shard_rules=)``
on dp2 x mp2 against the same three steps on a mesh of one of those chips,
and (b) the paged engine with ``mesh=`` mp4 against the unsharded engine.

Tolerances (stated here, asserted below):

* kernels: relative Frobenius error ``|a-b|/|b|`` <= 3e-2 for the flash
  output and each of dq/dk/dv, <= 2e-2 for the paged kernel against its
  twin.  Operands are bf16 (eps 2^-8 = 3.9e-3) and the two sides round at
  different places; a wrong kernel is off by O(1).
* mesh train: per-step losses within 5e-2 absolute of the one-chip run
  (loss ~ ln 50304 = 10.8; bf16 partial sums differ in order).
* tokens: the int8 engine quantizes what the bf16 one stores, against
  the near-flat logits of a random model, so only the identical share of
  their tokens is reported.  The mp4 comparison (760M: four heads of 96
  a chip are no whole tiles, so both sides run the XLA twin) runs in
  float32: GSPMD all-reduces bf16 partial sums in bf16 (``bf16[B,50304]``
  at the logits), which flips a random model's argmax on one ulp, while
  f32 partial sums leave token identity a sharp check (first tokens equal,
  identical share >= 0.5).

Every line before the last is one JSON object of per-phase facts (seconds,
device kind, bytes) — observations of one run, not results.  The last line
is exactly ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.core import compile_cache
from paddle_tpu.io import Window, native
from paddle_tpu.jit import CompiledTrainStep
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels._shapes import NEG_INF
from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                               GPTPretrainingCriterion)
from paddle_tpu.profiler import counters
from paddle_tpu.serving import LLMEngine

FLASH_TOL = 3e-2
PAGED_TOL = 2e-2
MESH_LOSS_TOL = 5e-2
MESH_SHARE_FLOOR = 0.5


def _check(ok, why):
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not ok:
        raise AssertionError(why)


def _emit(**facts):
    print(json.dumps(facts), flush=True)


def _rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _hooks_off():
    return not (fa._INTERPRET[0] or pa._INTERPRET[0])


def _check_compiled(jitted, *args):
    """The kernel must be in the program as a Mosaic custom call.  Under
    the tests' interpret hook (CPU rehearsal) there is nothing to lower."""
    if _hooks_off():
        _check("tpu_custom_call" in jitted.lower(*args).as_text(),
               "kernel did not lower to a Mosaic custom call")


def _device_facts():
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    return {"device_kind": dev.device_kind,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def _gpt760m(**kw):
    kw.setdefault("dtype", "bfloat16")
    return GPTConfig.gpt3_760m(vocab_size=50304, max_seq_len=1024, **kw)


def _train_cfg():
    # exactly bench.py's gpt760m leg
    return _gpt760m(use_flash_attention=True, recompute="selective_lean")


def _serve_cfg(**kw):
    # exactly bench.py's serving legs, at the 760M width
    return _gpt760m(use_flash_attention=False, recompute=None, **kw)


def _serve_cfg_xl():
    # the benchmark's serving configuration: heads of 128 are whole lane
    # tiles, which the paged decode kernel's block DMAs need
    return GPTConfig.gpt3_1_3b(vocab_size=50304, max_seq_len=1024,
                               dtype="bfloat16", use_flash_attention=False,
                               recompute=None)


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------
def _flash_check(shape, seed):
    args = [jax.random.normal(kk, shape, jnp.bfloat16)
            for kk in jax.random.split(jax.random.key(seed), 4)]

    def fwd_bwd(attn):
        def f(q, k, v, w):       # w: a random cotangent for the output
            out = attn(q, k, v, causal=True)
            return jnp.sum(out.astype(jnp.float32)
                           * w.astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    kern, ref = fwd_bwd(fa.flash_attention_fwd), fwd_bwd(
        fa.reference_attention)
    _check_compiled(kern, *args)
    (_, out_k), grads_k = kern(*args)
    (_, out_r), grads_r = ref(*args)
    errs = {"out": _rel_err(out_k, out_r)}
    errs.update({n: _rel_err(a, b)
                 for n, a, b in zip(("dq", "dk", "dv"), grads_k, grads_r)})
    for name, err in errs.items():
        _check(err <= FLASH_TOL,
               f"flash {name}: rel err {err} > {FLASH_TOL}")
    t0 = time.perf_counter()
    jax.block_until_ready(kern(*args))
    return {"shape": list(shape), "rel_err": errs,
            "fwd_bwd_s": time.perf_counter() - t0}


def _gather_twin(q, pool_k, pool_v, layer, bt, pos, sk=None, sv=None, *,
                 scale):
    """``GPT.decode_paged``'s ``kernel="off"`` attention, standalone: gather
    each row's logical sequence ``pool[layer, bt] -> [B, S, nh, hd]``, mask
    to ``pos``, softmax, contract."""
    B, nh, hd = q.shape
    S = bt.shape[1] * pool_k.shape[2]
    gk, gv = pool_k[layer, bt], pool_v[layer, bt]
    if sk is not None:
        gk = pa.dequantize_kv(gk, sk[layer, bt])
        gv = pa.dequantize_kv(gv, sv[layer, bt])
    gk = gk.reshape(B, S, nh, hd)
    gv = gv.reshape(B, S, nh, hd)
    logits = jnp.einsum("bhd,bkhd->bhk", (q * scale).astype(jnp.float32),
                        gk.astype(jnp.float32))
    live = jnp.arange(S)[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None, :], logits, NEG_INF), -1)
    return jnp.einsum("bhk,bkhd->bhd", p.astype(gv.dtype),
                      gv).astype(jnp.float32)


def _paged_check(rows, nh, hd, bs, max_blocks, kv_dtype, seed, layers=2):
    n_blocks = rows * max_blocks + 1         # + the trash block 0
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (rows, nh, hd), jnp.bfloat16)
    pool = [jax.random.normal(x, (layers, n_blocks, bs, nh, hd),
                              jnp.bfloat16) for x in (kk, kv)]
    scales = []
    if kv_dtype:
        pool, scales = zip(*(pa.quantize_kv(x, kv_dtype) for x in pool))
    rng = np.random.RandomState(seed)
    bt = (rng.permutation(n_blocks - 1)[:rows * max_blocks] + 1).reshape(
        rows, max_blocks).astype(np.int32)
    pos = rng.randint(0, max_blocks * bs, size=rows).astype(np.int32)
    pos[0], pos[-1] = max_blocks * bs - 1, 0   # full row, one-token row
    args = (q, *pool, jnp.int32(layers - 1), jnp.asarray(bt),
            jnp.asarray(pos), *scales)
    scale = hd ** -0.5
    kern = jax.jit(lambda *a: pa.paged_decode_attention(*a, scale=scale))
    twin = jax.jit(lambda *a: _gather_twin(*a, scale=scale))
    _check_compiled(kern, *args)
    err = _rel_err(kern(*args), twin(*args))
    _check(err <= PAGED_TOL,
           f"paged decode ({kv_dtype or 'bf16'}): rel err {err} > {PAGED_TOL}")
    return err


def phase_kernels(flash_shape=(8, 1024, 16, 96), rows=8, heads=16,
                  head_dim=128, block_size=16, max_blocks=64, seed=0):
    t0 = time.perf_counter()
    flash = _flash_check(flash_shape, seed)
    paged = {kv or "bf16": _paged_check(rows, heads, head_dim, block_size,
                                        max_blocks, kv, seed)
             for kv in (None, "int8")}
    gc.collect()
    return {"phase": "kernels", "ok": True,
            "seconds": time.perf_counter() - t0, "compiled": _hooks_off(),
            "flash": flash,
            "paged_decode": {"rows": rows, "heads": heads,
                             "head_dim": head_dim, "block_size": block_size,
                             "rel_err_vs_gather_twin": paged},
            **_device_facts()}


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------
def _train_setup(cfg, batch, seq, seed):
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    rng = np.random.RandomState(seed)
    ids, labels = (rng.randint(0, cfg.vocab_size, (batch, seq)).astype(
        np.int32) for _ in range(2))
    return model, opt, (lambda m, x, l: crit(m(x), l)), ids, labels


@contextlib.contextmanager
def _no_flash_reference():
    """``flash_attention_fwd`` keeps a jnp reference branch for CPU tests
    and counts every call that takes it; inside, the count must not move."""
    before = counters.get("kernels.flash.reference_calls")
    yield
    taken = counters.get("kernels.flash.reference_calls") - before
    _check(taken == 0, f"flash attention took the jnp reference {taken}x")


def _run_steps(step, ids, labels, n):
    """Two warm-up dispatches (the single-step program traces twice: empty
    optimizer accumulators, then full), then ``n`` timed steps on the same
    batch.  Returns (compile_s, [step_s], [losses incl. warm-up])."""
    x, y = paddle.to_tensor(ids), paddle.to_tensor(labels)
    t0 = time.perf_counter()
    losses = [float(step(x, y).numpy()), float(step(x, y).numpy())]
    compile_s = time.perf_counter() - t0
    before = counters.snapshot()
    step_s = []
    for _ in range(n):
        t0 = time.perf_counter()
        loss = step(x, y)
        loss._data.block_until_ready()
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss.numpy()))
    retraces = counters.delta(before).get("jit.traces", 0)
    _check(retraces == 0, f"{retraces} retraces after warm-up")
    _check(np.all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return compile_s, step_s, losses


def phase_train(cfg=None, batch=8, seq=1024, fused_k=4, seed=0):
    t_phase = time.perf_counter()
    cfg = cfg or _train_cfg()
    model, opt, loss_fn, ids, labels = _train_setup(cfg, batch, seq, seed)
    step = CompiledTrainStep(model, loss_fn, opt, metrics=True)
    with _no_flash_reference():
        compile_s, step_s, losses = _run_steps(step, ids, labels, 3)
    step.sync()
    del step

    # the same model and optimizer under fused dispatch: K steps per launch
    fstep = CompiledTrainStep(model, loss_fn, opt, fused_steps=fused_k,
                              metrics=True)
    win = Window(tuple(paddle.to_tensor(np.stack([a] * fused_k))
                       for a in (ids, labels)), fused_k)
    before = counters.snapshot()
    t0 = time.perf_counter()
    with _no_flash_reference():
        w1 = fstep(win).numpy()
    fused_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    w2 = fstep(win).numpy()
    fused_window_s = time.perf_counter() - t0
    delta = counters.delta(before)
    _check(delta.get("jit.fused_windows", 0) == 2
           and not delta.get("jit.fused_fallback_steps", 0),
           f"window did not fuse: {delta}")
    _check(w1.shape == (fused_k,) and np.all(np.isfinite(w1))
           and np.all(np.isfinite(w2)), f"bad window losses: {w1} {w2}")
    _check(w2[-1] < losses[0], f"loss did not keep falling: {w2}")
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    fstep.sync()
    del fstep, model, opt
    gc.collect()
    return {"phase": "train", "ok": True,
            "seconds": time.perf_counter() - t_phase,
            "model": {"hidden": cfg.hidden_size, "layers": cfg.num_layers,
                      "heads": cfg.num_heads, "vocab": cfg.vocab_size,
                      "dtype": cfg.dtype, "params": n_params},
            "batch": [batch, seq], "recompute": cfg.recompute,
            "compile_s": compile_s, "step_s": step_s, "losses": losses,
            "fused_steps": fused_k, "fused_compile_s": fused_compile_s,
            "fused_window_s": fused_window_s,
            "fused_losses": [float(x) for x in w2],
            **_device_facts()}


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------
def _workload(cfg, rows, prompt_range, quantum, seed):
    """(lengths, prompts, warm-up prompts).  Lengths are multiples of
    ``quantum`` spanning ``prompt_range`` (both ends included): every
    engine program compiles its sampling tail (two vocab-wide sorts, ~20 s
    of the TPU compiler each), so the chunk remainders are kept to a few
    prefill buckets.  The warm-up set has the same lengths and other
    tokens: it compiles every program the measured pass needs without
    seeding its prefix cache."""
    lo, hi = prompt_range
    rng = np.random.RandomState(seed)
    lengths = quantum * rng.randint(-(-lo // quantum), hi // quantum + 1,
                                    size=rows)
    lengths[0], lengths[-1] = lo, hi
    sets = [[rng.randint(0, cfg.vocab_size, size=int(n)).tolist()
             for n in lengths] for _ in range(2)]
    return [int(n) for n in lengths], sets[0], sets[1]


def _drain(eng, prompts, new_tokens):
    """add_request every prompt, step until drained.  The step bound turns
    a scheduler that never converges into a failure, not a hung chip."""
    handles = [eng.add_request(p, max_new_tokens=new_tokens)
               for p in prompts]
    chunks = sum(-(-len(p) // eng.prefill_chunk) for p in prompts)
    for _ in range(4 * (chunks + new_tokens) + 64):
        if all(h.is_finished for h in handles):
            break
        eng.step()
    for h in handles:
        _check(h.is_finished and h.finish_reason == "length"
               and len(h.tokens) == new_tokens,
               f"request did not finish: {h}")
    return [list(map(int, h.tokens)) for h in handles]


def _serve_setup(cfg, rows, prompt_range, quantum, seed):
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    model.eval()
    return (model,) + _workload(cfg, rows, prompt_range, quantum, seed)


def _serve_run(model, prompts, warm_prompts, new_tokens, probe=None,
               **engine_kw):
    """One engine, one slot per prompt: the warm-up pass, then the measured
    pass with zero retraces.  Returns (tokens, facts); ``probe(engine)``
    adds facts that need the live engine, which is released before
    returning so the next one finds the memory free."""
    programs = counters.snapshot()
    eng = LLMEngine(model, max_slots=len(prompts), block_size=16,
                    **engine_kw)
    t0 = time.perf_counter()
    _drain(eng, warm_prompts, new_tokens)
    warm_s = time.perf_counter() - t0
    before, hists = counters.snapshot(), eng.histogram_snapshot()
    t0 = time.perf_counter()
    tokens = _drain(eng, prompts, new_tokens)
    wall_s = time.perf_counter() - t0
    retraces = counters.delta(before).get("serving.retraces", 0)
    _check(retraces == 0, f"{retraces} retraces after warm-up")
    ttft, itl = (eng.hists[n].delta(hists[n]).summary()
                 for n in ("serving.ttft_ns", "serving.itl_ns"))
    stats = eng.stats()
    traced = counters.delta(programs)
    facts = {"kv_kernel": stats["kv_kernel"], "kv_dtype": stats["kv_dtype"],
             "warm_s": warm_s, "wall_s": wall_s,
             "ttft_ms": {"mean": ttft["mean"] / 1e6,
                         "p50": ttft["p50"] / 1e6},
             "per_token_ms": {"mean": itl["mean"] / 1e6,
                              "p50": itl["p50"] / 1e6},
             "kv_pool_bytes": stats["kv_pool_bytes_per_chip"],
             "weight_bytes": stats["weight_bytes_per_chip"],
             "pallas_programs": traced.get("kernels.paged.pallas_programs",
                                           0),
             "xla_programs": traced.get("kernels.paged.xla_fallbacks", 0)}
    if probe is not None:
        facts.update(probe(eng))
    eng.release_kv()
    del eng
    gc.collect()
    return tokens, facts


def _share(a, b):
    same = sum(x == y for ta, tb in zip(a, b) for x, y in zip(ta, tb))
    return same / sum(len(t) for t in a)


def phase_serve(cfg=None, rows=8, prompt_range=(32, 512), quantum=32,
                new_tokens=32, seed=0):
    t_phase = time.perf_counter()
    cfg = cfg or _serve_cfg_xl()
    model, lengths, prompts, warm = _serve_setup(cfg, rows, prompt_range,
                                                 quantum, seed)
    runs, tokens = {}, {}
    for name, kw in (("bf16", {}), ("int8", {"kv_dtype": "int8"})):
        tokens[name], runs[name] = _serve_run(model, prompts, warm,
                                              new_tokens, **kw)
        _check(runs[name]["kv_kernel"] == "pallas"
               and runs[name]["pallas_programs"] >= 1
               and runs[name]["xla_programs"] == 0,
               f"{name} did not decode through the kernel: {runs[name]}")
    runs["int8"]["identical_token_share_vs_bf16"] = _share(
        tokens["int8"], tokens["bf16"])
    del model
    gc.collect()
    return {"phase": "serve", "ok": True,
            "seconds": time.perf_counter() - t_phase,
            "model": {"hidden": cfg.hidden_size, "layers": cfg.num_layers,
                      "heads": cfg.num_heads, "dtype": cfg.dtype},
            "requests": rows, "prompt_tokens": lengths,
            "new_tokens": new_tokens, "runs": runs, **_device_facts()}


# ---------------------------------------------------------------------------
# --chips 4: the mesh paths and what they are compared with
# ---------------------------------------------------------------------------
def _per_device(arr):
    """{device id: shape of the shard that device holds}."""
    return {s.device.id: list(s.data.shape) for s in arr.addressable_shards}


def _bytes_in_use(n_devices=4):
    """{device id: bytes_in_use}; every chip must hold something (a
    backend that reports no memory stats — the CPU rehearsal — says None)."""
    out = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
           for d in jax.devices()[:n_devices]}
    _check(all(b is None or b > 0 for b in out.values()),
           f"a device holds nothing: {out}")
    return out


def _check_split(arr, n_devices, ways):
    """``arr`` lives on ``n_devices`` devices, each holding 1/``ways``."""
    shards = arr.addressable_shards
    _check(len({s.device.id for s in shards}) == n_devices,
           f"on {len(shards)} devices, want {n_devices}")
    for s in shards:
        _check(s.data.size * ways == arr.size,
               f"shard {s.data.shape} of {arr.shape} is not 1/{ways}")


def phase_mesh_train(cfg=None, batch=8, seq=1024, seed=0):
    t_phase = time.perf_counter()
    cfg = cfg or _train_cfg()
    devs = jax.devices()[:4]
    # Megatron column/row rules; they agree with the placements GPT
    # declares, and go through the shard_rules entry point on purpose
    rules = ((r"qkv_w$", P(None, None, "mp")), (r"qkv_b$", P(None, "mp")),
             (r"proj_w$", P(None, "mp", None)),
             (r"fc1_w$", P(None, None, "mp")), (r"fc1_b$", P(None, "mp")),
             (r"fc2_w$", P(None, "mp", None)), (r"wte$", P("mp", None)))
    runs = {}
    for name, mesh in (
            ("one_chip", Mesh(np.array(devs[:1]).reshape(1, 1),
                              ("dp", "mp"))),
            ("dp2mp2", Mesh(np.array(devs).reshape(2, 2), ("dp", "mp")))):
        model, opt, loss_fn, ids, labels = _train_setup(cfg, batch, seq,
                                                        seed)
        step = CompiledTrainStep(model, loss_fn, opt, mesh=mesh,
                                 shard_rules=rules)
        with _no_flash_reference():
            compile_s, step_s, losses = _run_steps(step, ids, labels, 1)
        runs[name] = {"compile_s": compile_s, "step_s": step_s,
                      "losses": losses}
        if mesh.size > 1:
            params, _, opt_state, _, _ = step._state
            qkv = params["qkv_w"]
            moment = next(iter(opt_state["acc"]["moment1"].values()))
            _check_split(qkv, 4, 2)
            runs[name].update(
                qkv_w_shards=_per_device(qkv),
                adam_moment_shards=_per_device(moment),
                bytes_in_use=_bytes_in_use())
        step.sync()
        del step, model, opt
        gc.collect()
    diffs = [abs(a - b) for a, b in zip(runs["one_chip"]["losses"],
                                        runs["dp2mp2"]["losses"])]
    _check(max(diffs) <= MESH_LOSS_TOL,
           f"dp2mp2 losses off the one-chip run by {diffs}")
    return {"phase": "mesh_train", "ok": True,
            "seconds": time.perf_counter() - t_phase, "mesh": "dp2 x mp2",
            "batch": [batch, seq], "loss_abs_diff": diffs, "runs": runs,
            **_device_facts()}


def phase_mesh_serve(cfg=None, rows=4, prompt_range=(128, 512), quantum=128,
                     new_tokens=32, seed=0):
    t_phase = time.perf_counter()
    cfg = cfg or _serve_cfg(dtype="float32")   # see the module docstring
    model, lengths, prompts, warm = _serve_setup(cfg, rows, prompt_range,
                                                 quantum, seed)

    def shards(eng):
        qkv = eng.arena.get("weights")["lws"]["qkv_w"]
        pool = eng.arena.get("pool_k")
        _check_split(qkv, 4, 4)
        _check_split(pool, 4, 4)
        return {"qkv_w_shards": _per_device(qkv),
                "kv_pool_shards": _per_device(pool),
                "bytes_in_use": _bytes_in_use()}

    base_tokens, base = _serve_run(model, prompts, warm, new_tokens)
    tokens, sharded = _serve_run(
        model, prompts, warm, new_tokens, probe=shards,
        mesh=Mesh(np.array(jax.devices()[:4]), ("mp",)))
    first = [[t[0] for t in toks] for toks in (base_tokens, tokens)]
    _check(first[0] == first[1],
           f"mp4 and unsharded engines disagree on a first token: {first}")
    share = _share(tokens, base_tokens)
    _check(share >= MESH_SHARE_FLOOR, f"identical token share {share}")
    del model
    gc.collect()
    return {"phase": "mesh_serve", "ok": True,
            "seconds": time.perf_counter() - t_phase, "mesh": "mp4",
            "dtype": cfg.dtype, "requests": rows, "prompt_tokens": lengths,
            "new_tokens": new_tokens, "identical_token_share": share,
            "unsharded": base, "mp4": sharded, **_device_facts()}


# ---------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh paths (needs four chips)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found platform "
                         f"{dev.platform!r}; there is no CPU mode")
    if jax.device_count() < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {jax.device_count()}")

    _check(_hooks_off(), "interpret hook is on: kernels would not compile")
    cache = compile_cache.enable()
    _emit(phase="setup", jax=jax.__version__, device_kind=dev.device_kind,
          device_count=jax.device_count(), compile_cache_dir=cache,
          compile_cache_entries_at_start=(
              len(os.listdir(cache)) if os.path.isdir(cache) else 0),
          native_collator="built" if native.native_available()
          else "numpy path")

    t0 = time.perf_counter()
    phases = ((phase_mesh_train, phase_mesh_serve) if args.chips == 4
              else (phase_kernels, phase_train, phase_serve))
    for phase in phases:
        _emit(**phase(seed=args.seed))
    _emit(phase="total", seconds=time.perf_counter() - t0)
    _emit(ok=True, device={"platform": dev.platform,
                           "kind": dev.device_kind,
                           "count": jax.device_count()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
