"""Perf probe: sweep batch size and loss variants on the real chip."""
import time, json, sys
import numpy as np
import jax, jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.jit import CompiledTrainStep
from paddle_tpu.models import GPTConfig, GPTForCausalLM, GPTPretrainingCriterion


def run(batch, seq, fused_loss, iters=20, recompute=False):
    cfg = GPTConfig.gpt3_125m(vocab_size=50304, max_seq_len=seq,
                              dtype="bfloat16", use_flash_attention=True,
                              recompute=recompute)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    ids = paddle.randint(0, cfg.vocab_size, [batch, seq])
    labels = paddle.randint(0, cfg.vocab_size, [batch, seq])

    if fused_loss:
        def loss_fn(m, x, l):
            from paddle_tpu.core.dispatch import apply_op
            logits = m(x)
            def fn(lg, lb):
                lg = lg.astype(jnp.float32)
                lse = jax.nn.logsumexp(lg, -1)
                picked = jnp.take_along_axis(
                    lg, lb[..., None].astype(jnp.int32), -1)[..., 0]
                return jnp.mean(lse - picked)
            return apply_op("ce", fn, logits, l)
    else:
        def loss_fn(m, x, l):
            return crit(m(x), l)

    step = CompiledTrainStep(model, loss_fn, opt)
    step(ids, labels); step(ids, labels)
    loss = step(ids, labels); loss.numpy()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(ids, labels)
    loss.numpy()
    dt = time.perf_counter() - t0
    tps = batch * seq * iters / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    mfu = tps * 6 * n_params / 197e12
    print(json.dumps({"batch": batch, "seq": seq, "fused": fused_loss,
                      "recompute": recompute,
                      "tok_s": round(tps, 0), "ms_step": round(dt/iters*1e3, 2),
                      "mfu_6N": round(mfu, 4)}), flush=True)


if __name__ == "__main__":
    from paddle_tpu.core import compile_cache
    compile_cache.enable()
    for b, fused, rc in [(8, True, False), (16, True, True), (32, True, True)]:
        try:
            run(b, 1024, fused, recompute=rc)
        except Exception as e:
            print(json.dumps({"batch": b, "fused": fused, "rc": rc,
                              "error": str(e)[:200]}), flush=True)
