"""paddle.inference — deployment predictor API.

Reference analogue: AnalysisPredictor/AnalysisConfig
(/root/reference/paddle/fluid/inference/api/analysis_predictor.h,
paddle_inference_api.h) — load a serialized program + params, feed named
inputs, run, fetch named outputs.

TPU-native: the serialized program IS the jit.save StableHLO artifact
(paddle_tpu/jit — jax.export); XLA plays the role of the 290 IR fusion
passes and the TensorRT engine (compilation happens on load/first run).
The Config knobs that steer CUDA/TRT specifics are accepted and recorded
but are no-ops, so reference deployment scripts run unchanged.
"""

from __future__ import annotations

import numpy as np

from ..core.tensor import Tensor


class Config:
    """reference: AnalysisConfig (paddle_inference_api.h)."""

    def __init__(self, prog_file=None, params_file=None):
        # jit.save artifacts share a prefix; accept either the prefix or
        # the explicit .pdmodel path
        if prog_file and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[: -len(".pdmodel")]
        self._prefix = prog_file
        self._params_file = params_file
        self._memory_optim = False
        self._device = "tpu"
        self._device_id = 0

    def model_prefix(self):
        return self._prefix

    # -- accepted-but-delegated knobs (XLA owns these decisions) ------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._device, self._device_id = "tpu", device_id

    def disable_gpu(self):
        self._device = "cpu"

    def enable_memory_optim(self, x=True):
        self._memory_optim = x

    def switch_ir_optim(self, x=True):
        pass  # XLA always optimizes

    def enable_tensorrt_engine(self, *a, **kw):
        pass  # XLA:TPU is the engine

    def set_cpu_math_library_num_threads(self, n):
        pass

    def summary(self):
        return (f"Config(prefix={self._prefix!r}, device={self._device}:"
                f"{self._device_id}, memory_optim={self._memory_optim})")


class _Handle:
    """Input/output tensor handle (reference: ZeroCopyTensor)."""

    def __init__(self, name):
        self.name = name
        self._array = None

    def copy_from_cpu(self, arr):
        # the reference ZeroCopyTensor contract COPIES: the caller may
        # reuse/mutate its buffer before run()
        self._array = np.array(arr, copy=True, order="C")

    def copy_to_cpu(self):
        return np.asarray(self._array)

    def reshape(self, shape):
        if self._array is not None:
            self._array = self._array.reshape(shape)

    def shape(self):
        return list(self._array.shape) if self._array is not None else None


class Predictor:
    """reference: AnalysisPredictor — run() over named handles."""

    def __init__(self, config: Config):
        from ..jit import load
        if not config.model_prefix():
            raise ValueError("Config needs the jit.save artifact prefix")
        self._layer = load(config.model_prefix())
        import json
        import os
        meta_path = config.model_prefix() + ".pdmeta.json"
        if os.path.exists(meta_path):
            with open(meta_path) as f:
                n_inputs = len(json.load(f)["inputs"])
        else:
            n_inputs = 1
        self._in_names = [f"input_{i}" for i in range(n_inputs)]
        self._inputs = {n: _Handle(n) for n in self._in_names}
        self._out_names = []
        self._outputs = {}

    def get_input_names(self):
        return list(self._in_names)

    def get_input_handle(self, name):
        return self._inputs[name]

    def run(self):
        unset = [n for n in self._in_names
                 if self._inputs[n]._array is None]
        if unset:
            raise ValueError(
                f"inference inputs not set: {unset} — call "
                "get_input_handle(name).copy_from_cpu(...) first")
        args = [Tensor(self._inputs[n].copy_to_cpu())
                for n in self._in_names]
        out = self._layer(*args)
        outs = out if isinstance(out, (list, tuple)) else [out]
        self._out_names = [f"output_{i}" for i in range(len(outs))]
        self._outputs = {}
        for n, o in zip(self._out_names, outs):
            h = _Handle(n)
            h.copy_from_cpu(np.asarray(o.numpy() if isinstance(o, Tensor)
                                       else o))
            self._outputs[n] = h
        return True

    def get_output_names(self):
        return list(self._out_names)

    def get_output_handle(self, name):
        return self._outputs[name]


def create_predictor(config: Config):
    """reference: paddle_infer::CreatePredictor."""
    return Predictor(config)


class GenerationPredictor:
    """Deployment front end for causal-LM generation that routes every
    request through ``serving.LLMEngine`` (continuous batching over a
    device-resident paged K/V pool) instead of one ``GPT.generate`` program
    per request shape.

    reference analogue: the inference-deployment generation path
    (fused_multi_transformer serving); here the engine owns admission,
    batching, sampling, and eviction — the predictor is a thin façade:

        pred = inference.GenerationPredictor(model, max_slots=8)
        outs = pred.generate(prompts, max_new_tokens=64)   # blocking batch
        for tok in pred.stream(prompt, max_new_tokens=64): # token stream
            ...
    """

    def __init__(self, model, max_slots=8, max_seq_len=None, **engine_kw):
        from ..serving import LLMEngine
        self._engine = LLMEngine(model, max_slots=max_slots,
                                 max_seq_len=max_seq_len, **engine_kw)

    @property
    def engine(self):
        return self._engine

    def generate(self, prompts, **kw):
        """Blocking batch generation: list of prompts in, list of full
        np.int32 sequences (prompt + generated) out."""
        return self._engine.generate(prompts, **kw)

    def stream(self, prompt, **kw):
        """Submit one prompt and iterate its generated tokens as the
        engine produces them."""
        return iter(self._engine.add_request(prompt, **kw))

    def close(self):
        """Drain the engine: finish outstanding requests, refuse new."""
        return self._engine.drain()


def create_generation_predictor(model, **kw):
    """Build a GenerationPredictor (engine-backed generation service)."""
    return GenerationPredictor(model, **kw)
