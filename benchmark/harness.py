"""What every kind of cell needs from the harness: the program's model built
from a configuration file with the benchmark's weights in it, spans on the
profiler's clock, the traced part of a window, and the device's facts."""

from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

from benchmark import trace_reduce, weights


def span(name):
    """A host span in the profiler's own trace (nothing when no trace is
    being taken), so idle gaps of the device can be named by it."""
    return jax.profiler.TraceAnnotation(name)


def build_model(cfg, seed):
    """``GPTForCausalLM`` at the configuration's sizes, its parameters
    replaced by the benchmark's seeded weights (see ``weights.py``)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    if cfg["d_model"] != cfg["n_heads"] * cfg["d_head"]:
        raise ValueError("d_model != n_heads * d_head in the configuration")
    paddle.seed(int(seed) & 0x7FFFFFFF)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["d_model"],
        num_layers=cfg["n_layers"], num_heads=cfg["n_heads"],
        max_seq_len=cfg["n_ctx"], ffn_hidden_size=cfg["d_ff"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        initializer_range=cfg["initializer_range"],
        dtype=cfg["compute_dtype"], **cfg["program"]))
    made = weights.make_program(cfg, seed, cfg["compute_dtype"])
    named = dict(model.named_parameters())
    if set(named) != set(made):
        raise ValueError("the model's parameters are not the benchmark's: "
                         f"{sorted(set(named) ^ set(made))}")
    for name, p in named.items():
        if tuple(p.shape) != made[name].shape:
            raise ValueError(f"{name}: {tuple(p.shape)} in the program, "
                             f"{made[name].shape} in the benchmark")
        p._data = made[name]
    return model


def reference_params(cfg, seed):
    """The same weights for the reference: regenerated from the seed in the
    served type, then widened to float32."""
    made = weights.make(cfg, seed, cfg["compute_dtype"])
    return {n: x.astype(jnp.float32) for n, x in made.items()}


def free_device():
    gc.collect()
    jax.clear_caches()
    gc.collect()


def memory_peak_bytes():
    """Peak on the fullest chip (0 where the backend keeps no count, which
    is the CPU of a rehearsal)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


class Tracer:
    """Takes a profiler trace of part of a window; reduces it later.

    ``with tracer.window(): ...`` runs its body under ``start_trace`` /
    ``stop_trace`` with the ``bench.window`` span around it.  Reading the
    trace takes seconds, so it waits for ``reduce()``, which the kind calls
    once its window has closed.  The Python tracer is off: it would time
    every Python call of the host loop that the trace is there to watch.
    The trace goes to a temporary directory under ``TMPDIR`` and is removed
    once read, unless ``keep`` names a directory to leave it in."""

    def __init__(self, keep=None):
        self.t0 = self.t1 = None
        self._keep = keep
        self._dir = keep or tempfile.mkdtemp(prefix="bench_trace_")

    @contextlib.contextmanager
    def window(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=options)
        try:
            self.t0 = time.perf_counter()
            with span(trace_reduce.WINDOW_SPAN):
                yield self
            self.t1 = time.perf_counter()
        finally:
            jax.profiler.stop_trace()

    def reduce(self):
        try:
            return trace_reduce.reduce(trace_reduce.load(
                trace_reduce.find_xplane(self._dir)))
        finally:
            if not self._keep:
                shutil.rmtree(self._dir, ignore_errors=True)
