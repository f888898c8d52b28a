"""Selective recompute keeps the flash forward's output and log-sum-exp.

The flash custom VJP names its output ``flash_out`` and its log-sum-exp
``flash_lse``; ``models/gpt.py``'s ``_sel_policy`` keeps both, so the
backward reads them back instead of replaying the forward kernel.  Here, on
the CPU with the kernels interpreted, a 2-layer GPT's train step is checked
against the same step with the replay (the policy without those two names):
the same loss and gradients bit for bit, and one forward kernel where the
replay has two.  Full recompute still replays everything."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit import bind_layer_state, layer_state
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.models import GPTConfig, GPTForCausalLM, gpt

B, S, V = 2, 128, 128
_MODES = ["selective_lean", "selective"]


@pytest.fixture()
def interpret():
    fa._INTERPRET[0] = True
    yield
    fa._INTERPRET[0] = False


def _replay_policy(mode):
    """``_sel_policy`` without the flash names: the parent's replay."""
    names = ("qkv", "attn_out") + (("ffn_up",) if mode == "selective" else ())
    return jax.checkpoint_policies.save_only_these_names(*names)


def _model(recompute):
    paddle.seed(7)
    return GPTForCausalLM(GPTConfig(
        vocab_size=V, hidden_size=64, num_layers=2, num_heads=2,
        max_seq_len=S, use_flash_attention=True, recompute=recompute,
        dtype="bfloat16"))


def _loss(model):
    """The model's next-token loss as a pure function of its parameters."""
    saved = layer_state(model)[0]

    def loss(params, ids):
        bind_layer_state(model, params, {})
        try:
            with paddle.no_grad():
                logits = model(Tensor._wrap(ids))._data
        finally:
            bind_layer_state(model, saved, {})
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, ids[:, 1:, None], -1))
    return loss


def _inputs(model):
    params = layer_state(model)[0]
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, V)
    return params, ids


def _flash_fwd_calls(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"] == "flash_fwd"
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    n += _flash_fwd_calls(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    n += _flash_fwd_calls(sub)
    return n


@pytest.mark.parametrize("mode", _MODES)
def test_step_equals_the_replay_bit_for_bit(interpret, monkeypatch, mode):
    model = _model(mode)
    params, ids = _inputs(model)
    step = jax.jit(jax.value_and_grad(_loss(model)))
    loss, grads = step(params, ids)
    monkeypatch.setattr(gpt, "_sel_policy", _replay_policy)
    replay = jax.jit(jax.value_and_grad(_loss(model)))
    loss_r, grads_r = replay(params, ids)
    assert np.isfinite(float(loss))
    assert np.array_equal(np.asarray(loss), np.asarray(loss_r))
    assert grads.keys() == grads_r.keys()
    for k in grads:
        a = np.asarray(grads[k].astype(jnp.float32))
        assert np.array_equal(a, np.asarray(grads_r[k].astype(jnp.float32))), k
    assert any(np.any(np.asarray(g.astype(jnp.float32)) != 0)
               for g in grads.values())


@pytest.mark.parametrize("mode,replay,calls", [
    ("selective_lean", False, 1), ("selective", False, 1),
    ("selective_lean", True, 2), (True, False, 2)],
    ids=["selective_lean", "selective", "replay", "full"])
def test_the_forward_kernel_runs_once(interpret, monkeypatch, mode, replay,
                                      calls):
    """One ``flash_fwd`` in the step's gradient program under selective
    recompute; two under the replay and under full recompute."""
    model = _model(mode)
    if replay:
        monkeypatch.setattr(gpt, "_sel_policy", _replay_policy)
    jaxpr = jax.make_jaxpr(jax.grad(_loss(model)))(*_inputs(model))
    assert _flash_fwd_calls(jaxpr.jaxpr) == calls
