"""Time fwd / fwd+bwd / full step through the framework, and a pure-JAX
hand-written GPT-125M train step as the XLA ceiling."""
import time, json
import numpy as np
import jax, jax.numpy as jnp


def sync(r):
    leaves = jax.tree.leaves(r)
    np.asarray(leaves[0])  # force device->host of one leaf

def timeit(f, *a, iters=20):
    r = f(*a); sync(r)
    r = f(*a); sync(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = f(*a)
    sync(r)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


B, S, V, H, L, NH, F = 8, 1024, 50304, 768, 12, 12, 3072


def framework():
    import paddle_tpu as paddle
    from paddle_tpu.jit import CompiledTrainStep, layer_state
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.core.dispatch import apply_op
    from paddle_tpu.core.tensor import Tensor

    cfg = GPTConfig.gpt3_125m(vocab_size=V, max_seq_len=S, dtype="bfloat16",
                              use_flash_attention=True)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    ids = paddle.randint(0, V, [B, S])
    labels = paddle.randint(0, V, [B, S])

    def loss_fn(m, x, l):
        logits = m(x)
        def fn(lg, lb):
            lg = lg.astype(jnp.float32)
            lse = jax.nn.logsumexp(lg, -1)
            picked = jnp.take_along_axis(
                lg, lb[..., None].astype(jnp.int32), -1)[..., 0]
            return jnp.mean(lse - picked)
        return apply_op("ce", fn, logits, l)

    ms_fwd = ms_fwdbwd = -1.0
    step = CompiledTrainStep(model, loss_fn, opt)
    step(ids, labels); step(ids, labels)
    t0 = time.perf_counter()
    for _ in range(20):
        loss = step(ids, labels)
    loss.numpy()
    ms_step = (time.perf_counter() - t0) / 20 * 1e3
    print(json.dumps({"which": "framework", "fwd_ms": round(ms_fwd, 2),
                      "fwdbwd_ms": round(ms_fwdbwd, 2),
                      "step_ms": round(ms_step, 2)}), flush=True)


def pure_jax():
    key = jax.random.PRNGKey(0)
    dt = jnp.bfloat16
    p = {
        "wte": jax.random.normal(key, (V, H), dt) * 0.02,
        "wpe": jax.random.normal(key, (S, H), dt) * 0.02,
        "ln1_w": jnp.ones((L, H), dt), "ln1_b": jnp.zeros((L, H), dt),
        "qkv_w": jax.random.normal(key, (L, H, 3 * H), dt) * 0.02,
        "qkv_b": jnp.zeros((L, 3 * H), dt),
        "proj_w": jax.random.normal(key, (L, H, H), dt) * 0.02,
        "proj_b": jnp.zeros((L, H), dt),
        "ln2_w": jnp.ones((L, H), dt), "ln2_b": jnp.zeros((L, H), dt),
        "fc1_w": jax.random.normal(key, (L, H, F), dt) * 0.02,
        "fc1_b": jnp.zeros((L, F), dt),
        "fc2_w": jax.random.normal(key, (L, F, H), dt) * 0.02,
        "fc2_b": jnp.zeros((L, H), dt),
        "lnf_w": jnp.ones((H,), dt), "lnf_b": jnp.zeros((H,), dt),
    }
    from paddle_tpu.kernels.flash_attention import flash_attention_fwd

    def norm(x, w, b):
        xf = x.astype(jnp.float32)
        m = jnp.mean(xf, -1, keepdims=True)
        v = jnp.var(xf, -1, keepdims=True)
        return ((xf - m) * jax.lax.rsqrt(v + 1e-5)).astype(x.dtype) * w + b

    def block(h, lw):
        x = norm(h, lw["ln1_w"], lw["ln1_b"])
        qkv = x @ lw["qkv_w"] + lw["qkv_b"]
        q, k, v = jnp.split(qkv, 3, -1)
        q = q.reshape(B, S, NH, H // NH); k = k.reshape(B, S, NH, H // NH)
        v = v.reshape(B, S, NH, H // NH)
        o = flash_attention_fwd(q, k, v, causal=True).reshape(B, S, H)
        h = h + o @ lw["proj_w"] + lw["proj_b"]
        x = norm(h, lw["ln2_w"], lw["ln2_b"])
        f = jax.nn.gelu(x @ lw["fc1_w"] + lw["fc1_b"]) @ lw["fc2_w"] + lw["fc2_b"]
        return h + f

    def loss_fn(p, ids, labels):
        h = p["wte"][ids] + p["wpe"][jnp.arange(S)]
        stack = {k: p[k] for k in ["ln1_w", "ln1_b", "qkv_w", "qkv_b",
                                   "proj_w", "proj_b", "ln2_w", "ln2_b",
                                   "fc1_w", "fc1_b", "fc2_w", "fc2_b"]}
        def body(h, lw):
            return block(h, lw), None
        h, _ = jax.lax.scan(body, h, stack)
        h = norm(h, p["lnf_w"], p["lnf_b"])
        lg = (h @ p["wte"].T).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, -1)
        picked = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
        return jnp.mean(lse - picked)

    mstate = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
    vstate = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
    master = jax.tree.map(lambda x: x.astype(jnp.float32), p)

    @jax.jit
    def fwd(p, ids, labels):
        return loss_fn(p, ids, labels)

    @jax.jit
    def fwdbwd(p, ids, labels):
        return jax.value_and_grad(loss_fn)(p, ids, labels)

    def stepfn(p, master, m, v, ids, labels):
        loss, g = jax.value_and_grad(loss_fn)(p, ids, labels)
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
        m = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, m, g)
        v = jax.tree.map(lambda v, g: 0.999 * v + 0.001 * g * g, v, g)
        master = jax.tree.map(
            lambda w, m, v: w - 1e-4 * (m / (jnp.sqrt(v) + 1e-8) + 0.01 * w),
            master, m, v)
        p = jax.tree.map(lambda w, x: w.astype(x.dtype), master, p)
        return loss, p, master, m, v
    jstep = jax.jit(stepfn, donate_argnums=(0, 1, 2, 3))

    ids = jax.random.randint(key, (B, S), 0, V)
    labels = jax.random.randint(key, (B, S), 0, V)
    ms_fwd = timeit(fwd, p, ids, labels)
    ms_fwdbwd = timeit(fwdbwd, p, ids, labels)
    # step donates, so loop manually
    loss, p2, master, mstate, vstate = jstep(p, master, mstate, vstate, ids, labels)
    loss, p2, master, mstate, vstate = jstep(p2, master, mstate, vstate, ids, labels)
    t0 = time.perf_counter()
    for _ in range(20):
        loss, p2, master, mstate, vstate = jstep(p2, master, mstate, vstate,
                                                 ids, labels)
    np.asarray(loss)
    ms_step = (time.perf_counter() - t0) / 20 * 1e3
    print(json.dumps({"which": "pure_jax", "fwd_ms": round(ms_fwd, 2),
                      "fwdbwd_ms": round(ms_fwdbwd, 2),
                      "step_ms": round(ms_step, 2)}), flush=True)


if __name__ == "__main__":
    from paddle_tpu.core import compile_cache
    compile_cache.enable()
    pure_jax()
    framework()
