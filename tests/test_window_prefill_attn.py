"""The prefill chunk's banded attention (``kernels.window_attention.
window_prefill_attn``, in interpret mode) against its XLA twin and both
against a dense masked softmax in float64, at a small size on the CPU.

A row's K/V sit in a pool of 4-token blocks, 2 K/V heads of 16 under 6
query heads each.  A window layer's table is a ring of ``ceil((W + C) /
bs) + 1`` entries (logical block ``b`` at entry ``b mod n``), a full
layer's a table that never wraps.  Cases cover a chunk at position 0,
inside the first window, past it with the ring wrapped, a chunk shorter
than its bucket, band edges inside a block, and two bucket widths; some
shrink the query block and the key step so that a chunk takes several
blocks (one past its live queries) and a band several steps, masked and
not."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import window_attention as wa
from paddle_tpu.profiler import counters

BS, N, G, D = 4, 2, 6, 16
W = 16


@pytest.fixture()
def interpret_mode():
    pa._INTERPRET[0] = True
    yield
    pa._INTERPRET[0] = False


def _dense(qg, pool, layer, table, start, window, ring):
    """float64 softmax over every position up to the chunk's last query,
    masked to each query's band."""
    C = qg.shape[2]
    nblk = -(-(start + C) // BS)
    lb = np.arange(nblk)
    blocks = np.asarray(table)[lb % len(table) if ring else lb]
    rows = np.asarray(pool, np.float64)[layer, blocks].reshape(nblk * BS, -1)
    k = rows[:, :N * D].reshape(-1, N, D)
    v = rows[:, N * D:2 * N * D].reshape(-1, N, D)
    s = np.einsum("ngqd,knd->ngqk", np.asarray(qg, np.float64), k)
    qpos = start + np.arange(C)[:, None]
    kpos = np.arange(nblk * BS)[None, :]
    seen = kpos <= qpos
    if window is not None:
        seen &= kpos > qpos - window
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("ngqk,knd->ngqd", p / p.sum(-1, keepdims=True), v)


# (window, start, live queries, bucket width, query block, blocks a step)
_CASES = [
    pytest.param(W, 0, 8, 8, None, None, id="window-at-0"),
    pytest.param(W, 9, 8, 8, None, 2, id="window-inside-the-first"),
    pytest.param(W, 45, 8, 8, None, 2, id="window-ring-wrapped"),
    pytest.param(W, 46, 5, 8, None, 2, id="window-short-chunk"),
    pytest.param(W, 37, 19, 32, 8, 2, id="window-wide-bucket"),
    pytest.param(None, 0, 8, 8, None, None, id="full-at-0"),
    pytest.param(None, 13, 8, 8, None, 2, id="full-edge-in-a-block"),
    pytest.param(None, 41, 5, 8, None, 3, id="full-short-chunk"),
    pytest.param(None, 22, 27, 32, 8, 2, id="full-wide-bucket"),
]


@pytest.mark.parametrize("window,start,length,C,block_q,step", _CASES)
def test_the_kernel_is_the_twin_and_the_dense_softmax(
        interpret_mode, monkeypatch, window, start, length, C, block_q,
        step):
    if block_q:
        monkeypatch.setattr(wa, "_BLOCK_Q", block_q)
    if step:
        monkeypatch.setattr(wa, "_BLOCKS_PER_STEP", step)
    r = np.random.default_rng(start * 100 + C)
    n = -(-(W + C) // BS) + 1 if window else 16
    pool = jnp.asarray(r.standard_normal((2, 60, BS, 2 * N * D)), jnp.float32)
    table = jnp.asarray(r.permutation(np.arange(1, 60))[:n], jnp.int32)
    qg = jnp.asarray(r.standard_normal((N, G, C, D)), jnp.float32) * 0.3
    args = (qg, pool, 1, table, jnp.int32(start), jnp.int32(length), window)
    got = np.asarray(wa.window_prefill_attn(*args))
    twin = np.asarray(wa.window_prefill_attn_xla(*args))
    want = _dense(qg, pool, 1, table, start, window, window is not None)
    assert got.shape == twin.shape == (N, G, C, D)
    assert np.isfinite(got).all()
    live = np.s_[:, :, :length]
    np.testing.assert_allclose(got[live], twin[live], atol=2e-5)
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    np.testing.assert_allclose(twin[live], want[live], atol=2e-5)


def test_each_traced_call_counts_its_form(interpret_mode):
    pool = jnp.zeros((1, 8, BS, 2 * N * D), jnp.float32)
    qg = jnp.zeros((N, G, 8, D), jnp.float32)
    table = jnp.arange(1, 8, dtype=jnp.int32)
    before = counters.snapshot()
    for fold in (wa.window_prefill_attn, wa.window_prefill_attn_xla):
        fold(qg, pool, 0, table, jnp.int32(0), jnp.int32(8), W)
    d = counters.delta(before)
    assert d["kernels.window_attention.prefill.pallas"] == 1
    assert d["kernels.window_attention.prefill.xla"] == 1
