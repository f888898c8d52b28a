"""Kind ``train``: a pre-training job on one chip.

The timed path is ``CompiledTrainStep.__call__`` on a fresh seeded batch
every step, fed through ``io.DataLoader`` -> ``io.DevicePrefetcher``.
Set-up builds that one object, drives it through its first steps (which
also compile it) and hands the same object to the window; the reference
follows those first steps once the window has closed (see ``check``).

A cell's file gives ``batch``, ``seq``, ``check_steps``, ``trace_seconds``
and ``limits``.
"""

from __future__ import annotations

import collections
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu import io

from benchmark import compare, flops, harness, traffic, weights
from benchmark.reference import gpt as ref_gpt


# ---------------------------------------------------------------------------
# norms of leaves, on the device, for both sides
# ---------------------------------------------------------------------------
def _leaf_norms(leaf, x):
    x = x.astype(jnp.float32)
    if leaf in weights.LAYER_LEAVES:
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
    return jnp.sqrt(jnp.sum(x * x))


@functools.partial(jax.jit, static_argnums=(0,))
def _tensor_norms(name, a, b):
    """Per-leaf norms of ``a - b`` for one tensor in the program's layout."""
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return {leaf: _leaf_norms(leaf, x)
            for leaf, x in weights.split_program(name, d).items()}


@functools.partial(jax.jit, static_argnums=(0,))
def _leaf_diff_norms(leaf, a, b):
    return _leaf_norms(leaf, a.astype(jnp.float32) - b.astype(jnp.float32))


def program_norms(tensors, cfg, seed, minus_initial):
    """``{leaf: norms}`` of the program's tensors (``{name: array}`` in its
    own layout), or of their change from the seeded initial weights."""
    out = {}
    for name, x in tensors.items():
        base = (weights.make_program(cfg, seed, cfg["compute_dtype"],
                                     (name,))[name]
                if minus_initial else jnp.zeros((), jnp.float32))
        out.update(jax.device_get(_tensor_norms(name, x, base)))
    return out


def reference_change_norms(params, cfg, seed):
    out = {}
    for leaf, x in params.items():
        base = weights.make(cfg, seed, cfg["compute_dtype"], (leaf,))[leaf]
        out[leaf] = jax.device_get(_leaf_diff_norms(leaf, x, base))
    return out


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------
class _Batches(io.IterableDataset):
    """The cell's batches, row by row, for ``io.DataLoader`` to collate."""

    def __init__(self, seed, batch, seq, vocab):
        self.args = (seed, batch, seq, vocab)

    def __iter__(self):
        seed, batch, seq, vocab = self.args
        step = 0
        while True:
            ids, labels = traffic.train_batch(seed, step, batch, seq, vocab)
            for row in range(batch):
                yield ids[row], labels[row]
            step += 1


def build(cell, cfg, seed):
    """The compiled step with its state, and its feed."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import CompiledTrainStep
    from paddle_tpu.models import GPTPretrainingCriterion
    model = harness.build_model(cfg, seed)
    crit = GPTPretrainingCriterion()
    o = cfg["optimizer"]
    if o["name"] != "AdamW":
        raise ValueError(f"kind train knows AdamW, not {o['name']!r}")
    opt = paddle.optimizer.AdamW(
        o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters())
    step = CompiledTrainStep(model, lambda m, x, y: crit(m(x), y), opt)
    dataset = _Batches(seed, cell["batch"], cell["seq"], cfg["vocab_size"])
    feed = iter(io.DevicePrefetcher(
        io.DataLoader(dataset, batch_size=cell["batch"]), depth=2))
    names = [n for n, _ in model.named_parameters()]
    return step, feed, names


def _state_tensors(step, names, what):
    """``{tensor name: array}`` out of the step's device-resident state:
    the optimizer's first moments or its float32 master weights (keyed by
    the parameter's position), which is what its next step consumes."""
    params, _, opt_state, _, _ = step._state
    if what == "moment1":
        return {names[i]: x for i, x in opt_state["acc"]["moment1"].items()}
    # a float32 parameter is its own master weight
    master = {names[i]: x for i, x in opt_state["master"].items()}
    return {n: master.get(n, params[n]) for n in names}


def first_steps(step, feed, names, cell, cfg, seed):
    """Drive the step through its first ``check_steps`` batches by the
    window's own call and feed, and read what the reference is compared
    with: each loss, the first gradient's norms (the first moment after one
    step is ``(1 - beta1) * g``) and the change of the master weights."""
    losses, grad_norms = [], None
    for i in range(cell["check_steps"]):
        x, y = next(feed)
        losses.append(float(step(x, y).numpy()))
        if i == 0:
            m1 = program_norms(_state_tensors(step, names, "moment1"),
                               cfg, seed, minus_initial=False)
            scale = 1.0 / (1.0 - cfg["optimizer"]["beta1"])
            grad_norms = {k: v * scale for k, v in m1.items()}
    change = program_norms(_state_tensors(step, names, "master"), cfg, seed,
                           minus_initial=True)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def _loop(step, feed, seconds, done_at):
    """Steps for ``seconds``: at most one step queued behind the one that
    runs, so the device never waits and the host never runs far ahead.
    ``done_at`` collects the second each step was seen complete.  The loop
    returns once the last step's loss is ready."""
    inflight = collections.deque()
    t0 = time.perf_counter()
    while True:
        with harness.span("bench.next_batch"):
            x, y = next(feed)
        with harness.span("bench.train_step"):
            inflight.append(step(x, y))
        if len(inflight) > 1:
            with harness.span("bench.wait_step"):
                inflight.popleft()._data.block_until_ready()
            done_at.append(time.perf_counter())
        if time.perf_counter() - t0 >= seconds:
            break
    with harness.span("bench.wait_step"):
        inflight.popleft()._data.block_until_ready()
    done_at.append(time.perf_counter())


def window(step, feed, cell, seconds, trace, keep_trace=None):
    """The measured window.  With ``trace`` its last part runs under the
    profiler.  Returns the observations the metrics are read from."""
    from paddle_tpu.profiler import counters
    tokens_per_step = cell["batch"] * cell["seq"]
    before = counters.snapshot()
    done_at, obs = [], {}
    t0 = time.perf_counter()
    if trace:
        # the last ``trace_seconds`` of the window run under the profiler
        _loop(step, feed, max(seconds - cell["trace_seconds"], 0.0),
              done_at)
        tracer = harness.Tracer(keep_trace)
        traced = []
        with tracer.window():
            _loop(step, feed, cell["trace_seconds"], traced)
        obs["trace"] = tracer.reduce()
        obs["traced_steps"] = len(traced)
        obs["traced_s"] = tracer.t1 - tracer.t0
        obs["step_s"] = list(np.diff(traced))
        done_at += traced
    else:
        _loop(step, feed, seconds, done_at)
    t1 = time.perf_counter()
    obs.update(window_s=t1 - t0, steps=len(done_at),
               tokens=len(done_at) * tokens_per_step,
               counters=counters.delta(before), t_start=t0)
    return obs


# ---------------------------------------------------------------------------
# the reference's side
# ---------------------------------------------------------------------------
def reference_readings(cell, cfg, seed, prec="f32", batch_rows=None):
    """The reference through the same first steps.  ``prec`` other than
    ``"f32"`` is the control; ``batch_rows`` plants the fault "part of the
    batch left out, the mean taken over the rest"."""
    trainer = ref_gpt.Trainer(harness.reference_params(cfg, seed),
                              cfg["n_heads"], cfg["layer_norm_epsilon"],
                              cfg["optimizer"], prec)
    for i in range(cell["check_steps"]):
        ids, labels = traffic.train_batch(seed, i, cell["batch"],
                                          cell["seq"], cfg["vocab_size"])
        trainer.step(ids[:batch_rows], labels[:batch_rows])
    return {"losses": trainer.losses, "grad_norms": trainer.grad_norms[0],
            "change_norms": reference_change_norms(trainer.params, cfg,
                                                   seed)}


def check(prog, cell, cfg, seed):
    """``(numbers, where)`` of the program's readings against the
    reference's."""
    return compare.train_numbers(prog, reference_readings(cell, cfg, seed))


def dispose(step, feed):
    """Let go of everything the program holds on the device (the step
    object itself stays registered with the package's sync hooks)."""
    step.model = step.optimizer = step.loss_fn = None
    step._state = None
    feed.close()
    harness.free_device()


# ---------------------------------------------------------------------------
def calibrate(cell, cfg, seed, seconds, control):
    """The readings a limit is set from, for one seed: the program's numbers
    against the reference and, with ``control``, the 8-bit-float reference's and
    the planted faults' numbers against it.  No measured window."""
    step, feed, names = build(cell, cfg, seed)
    prog = first_steps(step, feed, names, cell, cfg, seed)
    dispose(step, feed)
    ref = reference_readings(cell, cfg, seed)
    out = {"program": compare.train_numbers(prog, ref),
           "ref_losses": ref["losses"], "prog_losses": prog["losses"]}
    if control:
        out["control_fp8"] = compare.train_numbers(
            reference_readings(cell, cfg, seed, prec="fp8"), ref)
        out["fault_half_batch"] = compare.train_numbers(
            reference_readings(cell, cfg, seed,
                               batch_rows=cell["batch"] // 2), ref)
    return out


def run(ctx):
    cell, cfg, seed = ctx["cell"], ctx["config"], ctx["seed"]
    step, feed, names = build(cell, cfg, seed)
    prog = first_steps(step, feed, names, cell, cfg, seed)
    obs = window(step, feed, cell, ctx["seconds"], ctx["trace"],
                 ctx.get("keep_trace"))
    peak = harness.memory_peak_bytes()
    dispose(step, feed)
    t_check = time.perf_counter()
    numbers, where = check(prog, cell, cfg, seed)
    obs["check_s"] = time.perf_counter() - t_check
    obs["train_flops_per_step"] = flops.train_step_flops(
        cfg, cell["batch"], cell["seq"])
    return {
        "attempted": obs["steps"], "failed": 0,
        "end_to_end": {"train_tokens_per_s": obs["tokens"]
                       / obs["window_s"]},
        "numbers": numbers, "where": where, "obs": obs,
        "memory_peak_bytes": peak, "t_window_start": obs["t_start"],
    }
