"""AOT compiles of the main path's Pallas kernels for a described TPU.

Interpret mode checks a kernel's arithmetic and skips everything Mosaic
decides: block-shape legality, tiling alignment, VMEM budget.  The TPU
compiler is installed beside the CPU backend and compiles for a chip that
is *described*, not attached (``jax.experimental.topologies``), so these
tests hand each kernel its real shapes on a ``v5e:2x2`` device and fail
with whatever the chip's compiler would say.  Nothing runs: a pass here
is not a chip run (``chip_smoke.py`` is).

Shapes are the ones ``chip_smoke.py`` and ``bench.py`` use for flash:
GPT-760M (16 heads of 96) and GPT-125M (12 heads of 64) at batch x 1024.
The paged decode kernel is compiled at the chat cell's own shape (GPT-3
XL: 16 rows x 128 blocks of 16 tokens, 1,025 blocks, 24 layers stacked,
16 heads of 128), alone and inside the whole ``decode_paged`` step.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import rms_norm as rn


@pytest.fixture(scope="module")
def chip():
    """One device of a described ``v5e:2x2``.  Described inside a fixture,
    never while the file is imported: only one process may load the TPU's
    library, and every xdist worker imports every test file."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: "
                    f"{type(e).__name__}: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    """A described-device executable can be written to the persistent
    compile cache but not read back without a chip (the next compile warns
    and recompiles) — keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(fn, *args, kernels):
    """Lower + compile for the described chip; each of ``kernels`` must be
    in the executable as a Mosaic custom call (not interpreted, not folded)
    whose instruction carries the kernel's own name: the profiler's trace
    names a device op by its instruction (benchmark/trace_reduce.py).
    Differentiated directly, as here, JAX wraps the name
    (``%transpose_jvp_flash_dq__.1``); inside the train step's
    ``scan(checkpoint(block))`` it stays bare (``%flash_dq.10``)."""
    assert not (fa._INTERPRET[0] or pa._INTERPRET[0] or rn._INTERPRET[0])
    text = jax.jit(fn).lower(*args).compile().as_text()
    for name in kernels:
        assert re.search(rf"%(\w+_)?{name}_*(\.\d+)? = [^\n]*custom-call\("
                         r'[^\n]*custom_call_target="tpu_custom_call"',
                         text), name


# (batch, seq, heads, head_dim): the 760M and 125M training shapes
_FLASH_SHAPES = [pytest.param(8, 1024, 16, 96, id="gpt760m"),
                 pytest.param(16, 1024, 12, 64, id="gpt125m")]


@pytest.mark.parametrize("b,s,h,d", _FLASH_SHAPES)
def test_flash_fwd_compiles(chip, b, s, h, d):
    x = _sds(chip, (b, s, h, d), jnp.bfloat16)
    _compile(lambda q, k, v: fa._flash_bshd(
        q, k, v, True, d ** -0.5), x, x, x, kernels=["flash_fwd"])


@pytest.mark.parametrize("b,s,h,d", _FLASH_SHAPES)
def test_flash_fwd_bwd_compiles(chip, b, s, h, d):
    x = _sds(chip, (b, s, h, d), jnp.bfloat16)

    def loss(q, k, v):
        out = fa._flash_bshd(q, k, v, True, d ** -0.5)
        return jnp.sum(out.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x,
             kernels=["flash_fwd", "flash_dkv", "flash_dq"])


def _gpt_block_step(chip, monkeypatch, L, B, S, policy=None):
    """``jax.grad`` of an ``L``-layer GPT at GPT-760M's width (16 heads of
    96) under ``selective_lean``, ``B`` x ``S`` tokens, compiled for the
    chip; ``policy`` stands in for ``gpt._sel_policy``."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import bind_layer_state, layer_state
    from paddle_tpu.models import GPTConfig, GPTForCausalLM, gpt
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    if policy is not None:
        monkeypatch.setattr(gpt, "_sel_policy", policy)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=1024, hidden_size=1536, num_layers=L, num_heads=16,
        max_seq_len=S, use_flash_attention=True, recompute="selective_lean",
        dtype="bfloat16"))
    params = layer_state(model)[0]

    def loss(p, ids):
        bind_layer_state(model, p, {})
        try:
            with paddle.no_grad():
                logits = model(Tensor._wrap(ids))._data
        finally:
            bind_layer_state(model, params, {})
        return jnp.mean(logits.astype(jnp.float32))

    shapes = {k: _sds(chip, v.shape, v.dtype) for k, v in params.items()}
    return jax.jit(jax.grad(loss)).lower(
        shapes, _sds(chip, (B, S), jnp.int32)).compile()


def test_gpt760m_block_step_runs_the_flash_forward_once(chip, monkeypatch):
    """Under ``selective_lean`` the backward reads the flash forward's kept
    output and log-sum-exp: one ``%flash_fwd`` Mosaic call, where the
    replay (the policy without the flash names) has two.  The peak grows
    by the kept residuals and no more: per layer the output with heads
    merged (no padded lane) and the lane-dense fp32 log-sum-exp."""
    L, B, S, H, D = 2, 4, 2048, 16, 96
    fwd = (r"%flash_fwd(\.\d+)? = [^\n]*custom-call\("
           r'[^\n]*custom_call_target="tpu_custom_call"')
    kept = _gpt_block_step(chip, monkeypatch, L, B, S)
    assert len(re.findall(fwd, kept.as_text())) == 1
    replay = _gpt_block_step(
        chip, monkeypatch, L, B, S, lambda mode:
        jax.checkpoint_policies.save_only_these_names("qkv", "attn_out"))
    assert len(re.findall(fwd, replay.as_text())) == 2
    residuals = L * (B * S * H * D * 2 + B * H * S * 4)
    grew = (kept.memory_analysis().peak_memory_in_bytes
            - replay.memory_analysis().peak_memory_in_bytes)
    # a few small bookkeeping buffers move by a KiB between the programs
    assert grew <= residuals + 4096, (grew, residuals)


# (rows, blocks a row, blocks, layers, heads, head_dim): the chat cell's
# own arena (GPT-3 XL, 16 slots x 2048 tokens in 16-token blocks) and what
# one chip of an mp2 mesh holds of it; and the docqa cell's (Olmo-Hybrid's 4
# full-attention layers, 30 heads of 128 stored as 32, 16 slots x 16,896)
_PAGED_SHAPES = [pytest.param(16, 128, 1025, 24, 16, 128, id="gpt1.3b-chat"),
                 pytest.param(16, 128, 1025, 24, 8, 128, id="gpt1.3b-mp2"),
                 pytest.param(16, 1056, 4097, 4, 32, 128,
                              id="olmo-hybrid-docqa")]


@pytest.mark.parametrize("B,max_blocks,n_blocks,L,nh,hd", _PAGED_SHAPES)
@pytest.mark.parametrize("kv_dtype", [None, "int8", "fp8"])
def test_paged_decode_compiles(chip, B, max_blocks, n_blocks, L, nh, hd,
                               kv_dtype):
    bs = 16
    pool = _sds(chip, (L, n_blocks, bs, nh, hd),
                pa.KV_DTYPES[kv_dtype] if kv_dtype else jnp.bfloat16)
    args = [_sds(chip, (B, nh, hd), jnp.bfloat16), pool, pool,
            _sds(chip, (), jnp.int32),
            _sds(chip, (B, max_blocks), jnp.int32),
            _sds(chip, (B,), jnp.int32)]
    if kv_dtype:
        scales = _sds(chip, (L, n_blocks, bs), jnp.float32)
        args += [scales, scales]
    _compile(lambda *a: pa.paged_decode_attention(*a, scale=1.0), *args,
             kernels=["paged_decode_attn"])


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_decode_step_updates_the_arena_in_place(chip, kv_dtype):
    """The whole ``decode_paged`` step at the chat cell's shape: the kernel
    is in it, the stacked pools alias through, and nothing the size of a
    layer's pool (67 MB) is copied, widened or kept as a temporary."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    L, B, bs, max_blocks, n_blocks, nh, hd = 24, 16, 16, 128, 1025, 16, 128
    # a toy model lends its weights' tree; every leaf is given the shape
    # GPT-3 XL has there (hidden 16 -> 2048, 3x and 4x of it, the vocab)
    toy = GPTForCausalLM(GPTConfig(
        vocab_size=32, hidden_size=nh, num_layers=L, num_heads=nh,
        max_seq_len=2048, use_flash_attention=False))
    real = GPTForCausalLM.__new__(GPTForCausalLM)
    real.config = GPTConfig(
        vocab_size=50304, hidden_size=nh * hd, num_layers=L, num_heads=nh,
        max_seq_len=2048, use_flash_attention=False)
    grow = {nh: nh * hd, 3 * nh: 3 * nh * hd, 4 * nh: 4 * nh * hd,
            32: 50304}
    w = jax.tree_util.tree_map(
        lambda x: _sds(chip, tuple(grow.get(d, d) for d in x.shape),
                       jnp.bfloat16), toy.decode_state())
    pool = _sds(chip, (L, n_blocks, bs, nh, hd),
                pa.KV_DTYPES[kv_dtype] if kv_dtype else jnp.bfloat16)
    pools = [pool, pool]
    if kv_dtype:
        pools += [_sds(chip, (L, n_blocks, bs), jnp.float32)] * 2

    def decode(w, bt, tok, pos, *pools):
        return real.decode_paged(w, tok, pos, bt, *pools, kernel="pallas")

    compiled = jax.jit(decode, donate_argnums=(4, 5, 6, 7)[:len(pools)]) \
        .lower(w, _sds(chip, (B, max_blocks), jnp.int32),
               _sds(chip, (B,), jnp.int32), _sds(chip, (B,), jnp.int32),
               *pools).compile()
    text = compiled.as_text()
    assert re.search(r"%paged_decode_attn(\.\d+)? = [^\n]*custom-call\(",
                     text)
    assert not re.search(r"\[(24,)?1025,16,16,128\]\S* (copy|convert)\(",
                         text)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2 ** 20, mem
    assert mem.alias_size_in_bytes >= 2 * L * n_blocks * bs * nh * hd * (
        1 if kv_dtype else 2), mem


def test_rms_norm_compiles(chip):
    # 8 x 1024 rows at the 760M width; entered below rms_norm()'s
    # platform gate, which sees the CPU here
    _compile(rn._rms_norm_pallas, _sds(chip, (8192, 1536), jnp.bfloat16),
             _sds(chip, (1536,), jnp.bfloat16), kernels=["rms_norm"])


def test_hybrid_decode_step_updates_both_caches_in_place(chip):
    """``OlmoHybridForCausalLM.decode_paged`` at the docqa cell's shape:
    the block-table walk is in it over the padded pool, pools and recurrent
    state alias through, no stacked weight is copied or re-laid out for the
    scan over periods (a slice of a period's weights once cost 2 GB of
    temporaries a launch), and nothing the size of a pool or of the state
    is kept as a temporary."""
    import json
    import os
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                               OlmoHybridForCausalLM,
                                               param_shapes)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        hf = json.load(f)
    real = OlmoHybridForCausalLM.__new__(OlmoHybridForCausalLM)
    real.__dict__["config"] = c = OlmoHybridConfig.from_hf(
        hf, dtype="bfloat16")
    H, dk, dv = 30, 96, 192
    w = {n: _sds(chip, shape, dt)
         for n, (shape, _, dt) in param_shapes(c).items()}
    assert w["gate_w"].shape == (16, 3840, 11008)
    assert w["lin_qkv_w"].shape == (12, 3840, 11520)
    B, bs, max_blocks, n_blocks, nhp, hd = 16, 16, 1056, 4097, 32, 128
    assert pa.pool_heads(c.num_heads, hd) == nhp
    pool = _sds(chip, (4, n_blocks, bs, nhp, hd), jnp.bfloat16)
    spec = real.cache_spec()["slot_state"]
    st = {n: _sds(chip, tuple(lead) + (B,) + tuple(per), dt)
          for n, (lead, per, dt) in spec.items()}

    def decode(w, pk, pv, st, bt, tok, pos, running):
        return real.decode_paged(w, tok, pos, bt, pk, pv, st, running,
                                 kernel="pallas")

    compiled = jax.jit(decode, donate_argnums=(1, 2, 3)).lower(
        w, pool, pool, st, _sds(chip, (B, max_blocks), jnp.int32),
        _sds(chip, (B,), jnp.int32), _sds(chip, (B,), jnp.int32),
        _sds(chip, (B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert re.search(r"%paged_decode_attn(\.\d+)? = [^\n]*custom-call\(",
                     text)
    assert not re.search(r"bf16\[(16|12|4),3840,\d+\]\S* copy\(", text)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2 ** 20, mem
    state_bytes = 12 * B * (H * dk * dv * 4 + 3 * 11520 * 2)
    assert mem.alias_size_in_bytes >= (
        2 * 4 * n_blocks * bs * nhp * hd * 2 + state_bytes), mem


def test_gdn_chunk_compiles_at_the_docqa_chunk(chip):
    """The chunked delta rule at the docqa cell's chunk (512 positions, 30
    heads of ``d_k`` 96 / ``d_v`` 192, float32: neither width is whole
    lanes): plain XLA, so what could go wrong is a refusal of the blocked
    solve's shapes or a library ``triangular_solve`` coming back."""
    from paddle_tpu.kernels import gated_delta as gd
    B, T, H, dk, dv = 1, 512, 30, 96, 192
    f32 = lambda *shape: _sds(chip, shape, jnp.float32)      # noqa: E731
    compiled = jax.jit(gd.gdn_chunk).lower(
        f32(B, T, H, dk), f32(B, T, H, dk), f32(B, T, H, dv), f32(B, T, H),
        f32(B, T, H), f32(B, H, dk, dv)).compile()
    assert "triangular" not in compiled.as_text().lower()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


# ---------------------------------------------------------------------------
# the held experts' grouped products (kernels/moe.py) at the two expert
# cells' shapes: (token, expert) pairs, stacked layers, held experts, K, N
# ---------------------------------------------------------------------------
def _experts_on_the_chip(monkeypatch):
    """``moe.kernel_mode`` asks ``on_tpu()``, which sees the CPU here: the
    whole-program compiles answer for the described chip."""
    from paddle_tpu.kernels import moe
    monkeypatch.setattr(moe, "on_tpu", lambda: True)


def _held_products_are_the_kernel(text, layers=1):
    """Both grouped products of every expert layer (``layers`` of them in
    the program's text: a scan's body holds one period) are the Pallas
    kernel, under the name the trace's readers look for, and the
    compiler's own grouped matmul is gone."""
    assert len(re.findall(r"%ragged-dot-held[\w.]* = [^\n]*custom-call\("
                          r'[^\n]*custom_call_target="tpu_custom_call"',
                          text)) == 2 * layers
    assert not re.search(r"%ragged-dot-(none|metadata)", text)


_GROUPED_SHAPES = [
    pytest.param(128 * 8, 6, 128, 2048, 1536, "bfloat16",
                 id="sdar-decode-gate-up"),
    pytest.param(128 * 8, 6, 128, 768, 2048, "float32",
                 id="sdar-decode-down"),
    pytest.param(512 * 8, 6, 128, 2048, 1536, "bfloat16",
                 id="sdar-chunk-gate-up"),
    pytest.param(512 * 8, 6, 128, 768, 2048, "float32",
                 id="sdar-chunk-down"),
    pytest.param(32 * 6, 4, 40, 5120, 3072, "bfloat16",
                 id="deepseek-decode-gate-up"),
    pytest.param(32 * 6, 4, 40, 1536, 5120, "float32",
                 id="deepseek-decode-down"),
    pytest.param(1024 * 6, 4, 40, 5120, 3072, "bfloat16",
                 id="deepseek-chunk-gate-up"),
    pytest.param(1024 * 6, 4, 40, 1536, 5120, "float32",
                 id="deepseek-chunk-down"),
]


@pytest.mark.parametrize("pairs,L,E,K,N,out", _GROUPED_SHAPES)
def test_grouped_mm_compiles(chip, pairs, L, E, K, N, out):
    """The weight-stationary kernel alone, the stacked experts of every
    layer as one operand and the applied layer traced: gate beside up in
    the compute dtype, down in float32."""
    from paddle_tpu.kernels import moe
    tile = moe.row_tile(pairs, E, jnp.bfloat16)
    R = -(-(pairs + E * (tile - 1)) // tile) * tile
    _compile(lambda xs, w, count, layer: moe.grouped_mm_pallas(
        xs, w, count, layer, tile, jnp.dtype(out)),
        _sds(chip, (R, K), jnp.bfloat16), _sds(chip, (L, E, K, N), jnp.bfloat16),
        _sds(chip, (E,), jnp.int32), _sds(chip, (), jnp.int32),
        kernels=["ragged-dot-held"])


# ---------------------------------------------------------------------------
# DeepSeek-V2 at the longdoc cell's shape: 32 rows x 2,080 blocks of 16
# tokens, 16,385 blocks, 5 layers stacked, 128 heads over rows of 640
# ---------------------------------------------------------------------------
def _deepseek(chip):
    import json
    from paddle_tpu.models import deepseek_v2 as ds
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "deepseek-v2.json")) as f:
        hf = json.load(f)
    real = ds.DeepseekV2ForCausalLM.__new__(ds.DeepseekV2ForCausalLM)
    real.__dict__["config"] = c = ds.DeepseekV2Config.from_hf(
        hf, experts_held=(hf["experts_held_first"], hf["n_routed_experts"]),
        n_routed_experts=hf["published"]["n_routed_experts"],
        dtype="bfloat16")
    w = {n: _sds(chip, shape, dt)
         for n, (shape, _, dt) in ds.param_shapes(c).items()}
    assert w["expert_gu_w"].shape == (4, 40, 5120, 3072)
    assert w["router_w"].shape == (4, 5120, 160)
    st = {n: _sds(chip, tuple(shape), dt)
          for n, (shape, dt) in real.cache_spec()["step_state"].items()}
    return real, w, st


def test_mla_decode_compiles(chip):
    from paddle_tpu.kernels import mla_attention as mla
    B, max_blocks, n_blocks, row = 32, 2080, 16385, mla.pool_row(576)
    _compile(lambda q, pool, layer, bt, pos: mla.mla_decode_attn(
        q, pool, layer, bt, pos, 512),
        _sds(chip, (B, 128, row), jnp.bfloat16),
        _sds(chip, (5, n_blocks, 16, row), jnp.bfloat16),
        _sds(chip, (), jnp.int32), _sds(chip, (B, max_blocks), jnp.int32),
        _sds(chip, (B,), jnp.int32), kernels=["mla_decode_attn"])


def test_latent_decode_step_updates_the_pool_in_place(chip, monkeypatch):
    """``DeepseekV2ForCausalLM.decode_paged`` at the longdoc cell's shape:
    the latent walk is in it, the one pool and the expert counts alias
    through, no stacked weight is copied or sliced out for the scan over the
    expert layers (one layer's 40 experts are 1.9 GB), the grouped products
    are the weight-stationary kernel's two calls and none of the
    compiler's own ``ragged-dot``, nothing the size of the pool is kept as
    a temporary, and the only sorts are the sampling tail's two."""
    _experts_on_the_chip(monkeypatch)
    real, w, st = _deepseek(chip)
    B, max_blocks, n_blocks = 32, 2080, 16385
    pool = _sds(chip, (5, n_blocks, 16, 640), jnp.bfloat16)

    def decode(w, pool, st, bt, tok, pos, running):
        return real.decode_paged(w, tok, pos, bt, pool, None, st, running,
                                 kernel="pallas")

    compiled = jax.jit(decode, donate_argnums=(1, 2)).lower(
        w, pool, st, _sds(chip, (B, max_blocks), jnp.int32),
        _sds(chip, (B,), jnp.int32), _sds(chip, (B,), jnp.int32),
        _sds(chip, (B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert re.search(r"%mla_decode_attn(\.\d+)? = [^\n]*custom-call\(", text)
    _held_products_are_the_kernel(text)
    assert not re.search(r"bf16\[(4|5|40|160),\d{4,}[\d,]*\]\S* copy\(", text)
    assert not re.search(r" sort\(", text)      # the model's part has none
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2 ** 20, mem
    assert mem.alias_size_in_bytes >= 5 * n_blocks * 16 * 640 * 2, mem


def test_latent_prefill_chunk_compiles_in_place(chip, monkeypatch):
    """The widest chunk of the longdoc cell (1,024 tokens, 6,144 (token,
    expert) pairs of which a quarter are held here): the fold and the
    grouped products are the kernels (64-row tiles), the pool aliases
    through and no layer's 40 experts are copied or sliced out."""
    _experts_on_the_chip(monkeypatch)
    real, w, st = _deepseek(chip)
    max_blocks, n_blocks = 2080, 16385
    pool = _sds(chip, (5, n_blocks, 16, 640), jnp.bfloat16)

    def chunk(w, pool, st, ids, start, length, bt, slot):
        return real.prefill_paged(w, ids, start, length, bt, pool, None, st,
                                  slot, kernel="pallas")

    compiled = jax.jit(chunk, donate_argnums=(1, 2)).lower(
        w, pool, st, _sds(chip, (1, 1024), jnp.int32),
        _sds(chip, (), jnp.int32), _sds(chip, (), jnp.int32),
        _sds(chip, (max_blocks,), jnp.int32),
        _sds(chip, (), jnp.int32)).compile()
    text = compiled.as_text()
    assert re.search(r"%mla_prefill_attn[\w.]* = [^\n]*custom-call\(", text)
    _held_products_are_the_kernel(text)
    assert not re.search(r"bf16\[(4|5|40|160),\d{4,}[\d,]*\]\S* copy\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 5 * n_blocks * 16 * 640 * 2, mem
    assert mem.temp_size_in_bytes < 1536 * 2 ** 20, mem


@pytest.mark.parametrize("C", [1024, 8])
def test_mla_prefill_fold_compiles(chip, C):
    """A chunk's fold of one 2,048-key tile at the published widths (128
    heads, keys of 192, values of 128), at the widest bucket and the
    narrowest."""
    from paddle_tpu.kernels import mla_attention as mla
    H, K = 128, 2048
    state = (_sds(chip, (H, C, 1), jnp.float32),
             _sds(chip, (H, C, 1), jnp.float32),
             _sds(chip, (H, C, 128), jnp.float32))
    _compile(lambda q, k, v, q0, k0, *st: mla.mla_prefill_fold(
        q, k, v, q0, k0, st),
        _sds(chip, (H, C, 192), jnp.bfloat16),
        _sds(chip, (H, K, 192), jnp.bfloat16),
        _sds(chip, (H, K, 128), jnp.bfloat16), _sds(chip, (), jnp.int32),
        _sds(chip, (), jnp.int32), *state, kernels=["mla_prefill_attn"])


def _sdar(chip):
    import json
    from paddle_tpu.models import sdar
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "sdar-30b-a3b.json")) as f:
        hf = json.load(f)
    real = sdar.SdarMoeForCausalLM.__new__(sdar.SdarMoeForCausalLM)
    real.__dict__["config"] = c = sdar.SdarConfig.from_hf(
        hf, block_length=hf["block_length"],
        denoising_steps=hf["denoising_steps"],
        mask_token_id=hf["mask_token_id"], dtype="bfloat16")
    w = {n: _sds(chip, shape, dt)
         for n, (shape, _, dt) in sdar.param_shapes(c).items()}
    assert w["expert_gu_w"].shape == (6, 128, 2048, 1536)
    assert w["kv_w"].shape == (6, 2048, 1024)
    st = {n: _sds(chip, tuple(shape), dt)
          for n, (shape, dt) in real.cache_spec()["step_state"].items()}
    return real, w, st


def test_block_decode_attn_compiles(chip):
    """The chat-blocks cell's shape: 32 rows, per K/V head a query tile of
    8 heads x 4 positions, 4 K/V heads of 128 as rows of 1,024, the
    block's own four lines padded to a sublane tile."""
    from paddle_tpu.kernels import block_attention as ba
    S, max_blocks, n_blocks = 32, 320, 10241
    _compile(lambda q, new, pool, layer, bt, pos: ba.block_decode_attn(
        q, new, pool, layer, bt, pos, 4),
        _sds(chip, (S, 4, 32, 128), jnp.bfloat16),
        _sds(chip, (S, 4, 1024), jnp.bfloat16),
        _sds(chip, (6, n_blocks, 16, 1024), jnp.bfloat16),
        _sds(chip, (), jnp.int32), _sds(chip, (S, max_blocks), jnp.int32),
        _sds(chip, (S,), jnp.int32), kernels=["block_decode_attn"])


def test_block_decode_step_updates_the_pool_in_place(chip, monkeypatch):
    """``serving.block_decode.decode_program`` (``jit_decode`` of a
    block-decoding engine) at the chat-blocks cell's shape: the walk is in
    it, the one pool and the expert counts alias through, no stacked
    weight is copied or sliced out for the scan over the layers (one
    layer's 128 experts are 1.2 GB), the grouped products are the
    weight-stationary kernel's two calls and none of the compiler's own
    ``ragged-dot``, and the only sorts are the sampling tail's two."""
    from paddle_tpu.serving import block_decode as bd
    _experts_on_the_chip(monkeypatch)
    real, w, st = _sdar(chip)
    S, max_blocks, n_blocks = 32, 320, 10241
    pool = _sds(chip, (6, n_blocks, 16, 1024), jnp.bfloat16)
    i32, f32 = jnp.int32, jnp.float32
    compiled = jax.jit(
        bd.decode_program(real, "pallas", 4, real.config.mask_token_id),
        donate_argnums=(1, 2)).lower(
        w, pool, st, _sds(chip, (S, max_blocks), i32),
        _sds(chip, (S, 4), i32), _sds(chip, (S, 4), i32),
        _sds(chip, (S,), i32), _sds(chip, (S,), i32),
        _sds(chip, (S,), jnp.bool_), _sds(chip, (S, 2), jnp.uint32),
        _sds(chip, (S,), jnp.bool_), _sds(chip, (S,), f32),
        _sds(chip, (S,), i32), _sds(chip, (S,), f32), _sds(chip, (S,), i32),
        _sds(chip, (S,), f32)).compile()
    text = compiled.as_text()
    assert re.search(r"%block_decode_attn(\.\d+)? = [^\n]*custom-call\(",
                     text)
    _held_products_are_the_kernel(text)
    assert not re.search(r"bf16\[(6|128),\d{4,}[\d,]*\]\S* copy\(", text)
    assert len(re.findall(r" sort\(", text)) == 2
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 768 * 2 ** 20, mem
    assert mem.alias_size_in_bytes >= 6 * n_blocks * 16 * 1024 * 2, mem


def test_block_prefill_chunk_compiles_in_place(chip, monkeypatch):
    """The widest chunk of the chat-blocks cell (512 tokens): the pool
    aliases through, no layer's experts are sliced out (the grouped
    products are the kernel's, at 32-row tiles), and no logits are made
    (the head is not even an argument: nothing is sampled)."""
    _experts_on_the_chip(monkeypatch)
    real, w, st = _sdar(chip)
    n_blocks = 10241
    pool = _sds(chip, (6, n_blocks, 16, 1024), jnp.bfloat16)
    compiled = jax.jit(real.prefill_paged, donate_argnums=(5, 6)).lower(
        w, _sds(chip, (1, 512), jnp.int32), _sds(chip, (), jnp.int32),
        _sds(chip, (), jnp.int32), _sds(chip, (320,), jnp.int32), pool,
        st).compile()
    text = compiled.as_text()
    _held_products_are_the_kernel(text)
    assert not re.search(r"bf16\[(6|128),\d{4,}[\d,]*\]\S* copy\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 6 * n_blocks * 16 * 1024 * 2, mem
    assert mem.argument_size_in_bytes < 10_200_000_000, mem   # no head
    assert mem.temp_size_in_bytes < 512 * 2 ** 20, mem


# ---------------------------------------------------------------------------
# Trinity at the longmix cell's shape: 32 rows, a full table of 4,160 blocks
# of 16 tokens over 16,385 blocks (one full layer), a window ring of 385
# entries over 12,321 blocks (four window layers), rows [k ; v] of 2,048
# ---------------------------------------------------------------------------
def _trinity(chip):
    import json
    from paddle_tpu.models import trinity as tr
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity-large-preview.json")) as f:
        hf = json.load(f)
    real = tr.TrinityForCausalLM.__new__(tr.TrinityForCausalLM)
    real.__dict__["config"] = c = tr.TrinityConfig.from_hf(
        hf, experts_held=(hf["experts_held_first"], hf["num_experts"]),
        num_experts=hf["published"]["num_experts"], dtype="bfloat16")
    w = {n: _sds(chip, shape, dt)
         for n, (shape, _, dt) in tr.param_shapes(c).items()}
    assert w["expert_gu_w"].shape == (4, 32, 3072, 6144)
    assert w["router_w"].shape == (4, 3072, 256)
    st = {n: _sds(chip, tuple(shape), dt)
          for n, (shape, dt) in real.cache_spec()["step_state"].items()}
    pools = (_sds(chip, (1, 16385, 16, 2048), jnp.bfloat16),
             _sds(chip, (4, 12321, 16, 2048), jnp.bfloat16))
    return real, w, st, pools


@pytest.mark.parametrize("entries", [385, 4160], ids=["window", "full"])
def test_window_decode_attn_compiles(chip, entries):
    """The banded walk alone: per K/V head a query tile of 6 heads (padded
    to a sublane tile) over the band of a ring of 385 entries or of a full
    table of 4,160."""
    from paddle_tpu.kernels import window_attention as wa
    S = 32
    _compile(lambda q, pool, layer, table, pos, lo: wa.window_decode_attn(
        q, pool, layer, table, pos, lo, 8),
        _sds(chip, (S, 8, 6, 128), jnp.bfloat16),
        _sds(chip, (4, 12321, 16, 2048), jnp.bfloat16),
        _sds(chip, (), jnp.int32), _sds(chip, (S, entries), jnp.int32),
        _sds(chip, (S,), jnp.int32), _sds(chip, (S,), jnp.int32),
        kernels=["window_decode_attn"])


@pytest.mark.parametrize("C", [2048, 8])
@pytest.mark.parametrize("entries", [385, 4160], ids=["window", "full"])
def test_window_prefill_attn_compiles(chip, entries, C):
    """The chunk's banded attention alone, at the widest and the smallest
    bucket: 8 K/V heads of 6 query heads of 128, the window layers' ring of
    385 entries over their pool or the full layer's table of 4,160 over
    its own."""
    from paddle_tpu.kernels import window_attention as wa
    window, layers, blocks = ((4096, 4, 12321) if entries == 385
                              else (None, 1, 16385))
    _compile(lambda q, pool, layer, table, start, length:
             wa.window_prefill_attn(q, pool, layer, table, start, length,
                                    window),
             _sds(chip, (8, 6, C, 128), jnp.bfloat16),
             _sds(chip, (layers, blocks, 16, 2048), jnp.bfloat16),
             _sds(chip, (), jnp.int32), _sds(chip, (entries,), jnp.int32),
             _sds(chip, (), jnp.int32), _sds(chip, (), jnp.int32),
             kernels=["window_prefill_attn"])


def test_window_decode_step_updates_both_pools_in_place(chip, monkeypatch):
    """``TrinityForCausalLM.decode_paged`` at the longmix cell's shape: the
    banded walk runs every layer (four window layers and the full one),
    both pools and the expert counts alias through, no stacked weight is
    copied for the scan, the grouped products are the kernel's."""
    _experts_on_the_chip(monkeypatch)
    real, w, st, (pool, wpool) = _trinity(chip)
    B = 32

    def decode(w, pool, wpool, st, bt, tok, pos, running):
        return real.decode_paged(w, tok, pos, bt, pool, wpool, st, running,
                                 kernel="pallas", window_entries=385)

    compiled = jax.jit(decode, donate_argnums=(1, 2, 3)).lower(
        w, pool, wpool, st, _sds(chip, (B, 4160 + 385), jnp.int32),
        _sds(chip, (B,), jnp.int32), _sds(chip, (B,), jnp.int32),
        _sds(chip, (B,), jnp.bool_)).compile()
    text = compiled.as_text()
    # the dense window layer, and the period's window, full, window,
    # window: one walk each
    assert len(re.findall(r"%window_decode_attn(\.\d+)? = [^\n]*custom-call"
                          r"\(", text)) == 5
    _held_products_are_the_kernel(text, layers=4)
    assert not re.search(r"bf16\[(4|5|32),\d{4,}[\d,]*\]\S* copy\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= (1 + 4 * 12321 / 16385) * (
        16385 * 16 * 2048 * 2), mem
    assert mem.temp_size_in_bytes < 256 * 2 ** 20, mem


def test_window_prefill_chunk_compiles_in_place(chip, monkeypatch):
    """The widest chunk of the longmix cell (2,048 tokens): every layer's
    attention is the banded prefill kernel, both pools alias through, the
    grouped products are the kernel's, and the temporaries leave the
    weights and pools their 12.9 GB of the chip."""
    _experts_on_the_chip(monkeypatch)
    real, w, st, (pool, wpool) = _trinity(chip)

    def chunk(w, pool, wpool, st, ids, start, length, bt, slot):
        return real.prefill_paged(w, ids, start, length, bt, pool, wpool,
                                  st, slot, kernel="pallas",
                                  window_entries=385)

    compiled = jax.jit(chunk, donate_argnums=(1, 2, 3)).lower(
        w, pool, wpool, st, _sds(chip, (1, 2048), jnp.int32),
        _sds(chip, (), jnp.int32), _sds(chip, (), jnp.int32),
        _sds(chip, (4160 + 385,), jnp.int32),
        _sds(chip, (), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%window_prefill_attn(\.\d+)? = [^\n]*custom-call"
                          r"\(", text)) == 5
    _held_products_are_the_kernel(text, layers=4)
    assert not re.search(r"bf16\[(4|5|32),\d{4,}[\d,]*\]\S* copy\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= (1 + 4 * 12321 / 16385) * (
        16385 * 16 * 2048 * 2), mem
    assert mem.temp_size_in_bytes < 2 * 2 ** 30, mem


@pytest.mark.parametrize("R,T", [pytest.param(1, 512, id="chunk"),
                                 pytest.param(128, 1, id="decode")])
def test_selective_scan_compiles_in_place(chip, R, T):
    """The selective scan at the chat-burst cell's widths (5,120 channels,
    a state of 16): a 512-token chunk of one row and a decode step of 128
    rows, each over the 26-layer, 128-slot state array and the
    convolution's rows beside it, which alias through: no copy of either,
    and nothing of their size kept as a temporary."""
    from paddle_tpu.kernels import selective_scan as ss
    E, N = 5120, 16
    f32 = lambda *shape: _sds(chip, shape, jnp.float32)       # noqa: E731
    i32 = lambda *shape: _sds(chip, shape, jnp.int32)         # noqa: E731
    args = (f32(R, T, E), f32(R, T, E), f32(R, T, N), f32(R, T, N),
            f32(N, E), f32(E), f32(26, 128, N, E),
            _sds(chip, (26, 128) + ss.tail_shape(3, E), jnp.bfloat16),
            _sds(chip, (R,) + ss.tail_shape(3, E), jnp.bfloat16), i32(),
            i32(R),
            _sds(chip, (R,), jnp.bool_), _sds(chip, (R,), jnp.bool_))
    _compile(ss.selective_scan, *args, kernels=["selective_scan"])
    compiled = jax.jit(ss.selective_scan, donate_argnums=(6, 7)).lower(
        *args).compile()
    assert ss.tail_shape(3, E) == (8, 1920)
    assert not re.search(r"(f32\[26,128,16,5120|bf16\[26,128,8,1920)\]\S*"
                         r" copy\(",
                         compiled.as_text())
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 26 * 128 * (N * 4 + 3 * 2) * E, mem
    assert mem.temp_size_in_bytes < 64 * 2 ** 20, mem
