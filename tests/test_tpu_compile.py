"""AOT compiles of the main path's Pallas kernels for a described TPU.

Interpret mode checks a kernel's arithmetic and skips everything Mosaic
decides: block-shape legality, tiling alignment, VMEM budget.  The TPU
compiler is installed beside the CPU backend and compiles for a chip that
is *described*, not attached (``jax.experimental.topologies``), so these
tests hand each kernel its real shapes on a ``v5e:2x2`` device and fail
with whatever the chip's compiler would say.  Nothing runs: a pass here
is not a chip run (``chip_smoke.py`` is).

Shapes are the ones ``chip_smoke.py`` and ``bench.py`` use: GPT-760M
(16 heads of 96) and GPT-125M (12 heads of 64) at batch x 1024, a paged
pool of 16-token blocks holding 8 rows x 1024 tokens.
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
    x = _sds(chip, (b, h, s, d), jnp.bfloat16)
    _compile(lambda q, k, v: fa._flash_attention_bhsd(
        q, k, v, True, d ** -0.5), x, x, x, kernels=["flash_fwd"])


@pytest.mark.parametrize("b,s,h,d", _FLASH_SHAPES)
def test_flash_fwd_bwd_compiles(chip, b, s, h, d):
    x = _sds(chip, (b, h, s, d), jnp.bfloat16)

    def loss(q, k, v):
        out = fa._flash_attention_bhsd(q, k, v, True, d ** -0.5)
        return jnp.sum(out.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x,
             kernels=["flash_fwd", "flash_dkv", "flash_dq"])


@pytest.mark.parametrize("nh,hd", [pytest.param(16, 96, id="gpt760m"),
                                   pytest.param(12, 64, id="gpt125m")])
@pytest.mark.parametrize("kv_dtype", [None, "int8", "fp8"])
def test_paged_decode_compiles(chip, nh, hd, kv_dtype):
    B, bs, max_blocks = 8, 16, 64
    n_blocks = B * max_blocks + 1
    pool = _sds(chip, (n_blocks, bs, nh, hd),
                pa.KV_DTYPES[kv_dtype] if kv_dtype else jnp.bfloat16)
    args = [_sds(chip, (B, nh, hd), jnp.bfloat16), pool, pool,
            _sds(chip, (B, max_blocks), jnp.int32),
            _sds(chip, (B,), jnp.int32)]
    if kv_dtype:
        scales = _sds(chip, (n_blocks, bs), jnp.float32)
        args += [scales, scales]
    _compile(lambda *a: pa.paged_decode_attention(*a, scale=1.0), *args,
             kernels=["paged_decode_attn"])


def test_rms_norm_compiles(chip):
    # 8 x 1024 rows at the 760M width; entered below rms_norm()'s
    # platform gate, which sees the CPU here
    _compile(rn._rms_norm_pallas, _sds(chip, (8192, 1536), jnp.bfloat16),
             _sds(chip, (1536,), jnp.bfloat16), kernels=["rms_norm"])
