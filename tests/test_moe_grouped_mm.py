"""The held experts' grouped products: the weight-stationary Pallas kernel
(under the interpret hook) against ``jax.lax.ragged_dot`` and against a
dense per-expert loop, and ``held_expert_ffn`` with the kernel on against
the twin at the two expert models' rehearsal shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import moe
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.profiler import counters


@pytest.fixture()
def interpret_mode():
    pa._INTERPRET[0] = True
    yield
    pa._INTERPRET[0] = False


E, L, TILE = 6, 3, 8

# the rows each of the applied layer's E groups holds, then the rows past
# the last group (pairs held elsewhere)
_GROUPS = {
    "spread": ([3, 0, 9, 1, 8, 5], 4),
    "larger_than_the_tile": ([2, 21, 0, 0, 17, 1], 0),
    "one_row": ([0, 0, 1, 0, 0, 0], 6),
    "all_in_one_group": ([0, 0, 0, 30, 0, 0], 0),
    "all_in_the_first": ([30, 0, 0, 0, 0, 0], 0),
    "all_in_the_last": ([0, 0, 0, 0, 0, 30], 3),
    "none_held": ([0, 0, 0, 0, 0, 0], 12),
    "rows_past_the_last_group": ([4, 4, 0, 0, 0, 0], 22),
}


def _case(sizes, past, K, N, dtype, seed=0):
    """Packed rows (the twin's layout), the same rows at tile-aligned group
    starts (the kernel's), the stacked weights and where each packed row
    went."""
    r = np.random.default_rng(seed)
    sizes = np.asarray(sizes, np.int32)
    n = int(sizes.sum()) + past
    packed = r.standard_normal((n, K)).astype(np.float32)
    w = r.standard_normal((L, E, K, N)).astype(np.float32) / np.sqrt(K)
    starts = np.asarray(moe.group_starts(
        jnp.asarray(np.append(sizes, past)), TILE))
    group = np.repeat(np.arange(E + 1), np.append(sizes, past))
    first = np.cumsum(np.append(sizes, past)) - np.append(sizes, past)
    place = starts[group] + np.arange(n) - first[group]
    R = -(-(n + E * (TILE - 1)) // TILE) * TILE
    padded = np.zeros((R, K), np.float32)
    padded[place] = packed
    return (jnp.asarray(packed, dtype), jnp.asarray(padded, dtype),
            jnp.asarray(w, dtype), jnp.asarray(sizes), place, group)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("how", list(_GROUPS))
def test_kernel_is_ragged_dot_and_the_dense_loop(interpret_mode, how, dtype,
                                                 tol):
    """Every row of every group, whatever the groups' sizes, is its expert's
    product: against the twin on the packed rows and against one plain
    product an expert in float32; the other layers' experts are never
    applied."""
    sizes, past = _GROUPS[how]
    K, N = 32, 48
    packed, padded, w, count, place, group = _case(sizes, past, K, N, dtype)
    held = group < E
    for layer in (0, 2):
        got = moe.grouped_mm(padded, w, count, jnp.int32(layer), TILE,
                             jnp.float32)
        assert got.shape == (padded.shape[0], N) and got.dtype == jnp.float32
        got = np.asarray(got)[place][held]
        twin = np.asarray(moe.grouped_mm(packed, w, count, jnp.int32(layer),
                                         out_dtype=jnp.float32))[held]
        dense = np.stack(
            [np.asarray(packed[i], np.float32)
             @ np.asarray(w[layer, group[i]], np.float32)
             for i in np.flatnonzero(held)]) if held.any() else twin
        np.testing.assert_allclose(got, twin, atol=tol)
        np.testing.assert_allclose(got, dense, atol=tol)


@pytest.mark.parametrize("N,want_tiles", [(48, 1), (512, 4)])
def test_kernel_walks_tiles_of_columns(interpret_mode, monkeypatch, N,
                                       want_tiles):
    """With weights wider than one tile's bytes the grid walks tiles of
    columns inside an expert, and an empty group between two others
    repeats the tile fetched last."""
    K = 32
    monkeypatch.setattr(moe, "_WEIGHT_TILE_BYTES", K * 128 * 4)
    assert N // moe._col_tile(K, N, 4) == want_tiles
    packed, padded, w, count, place, group = _case(
        [5, 0, 0, 12, 0, 3], 5, K, N, jnp.float32, seed=1)
    got = np.asarray(moe.grouped_mm(padded, w, count, jnp.int32(1),
                                    TILE))[place][group < E]
    twin = np.asarray(moe.grouped_mm(packed, w, count,
                                     jnp.int32(1)))[group < E]
    np.testing.assert_allclose(got, twin, atol=1e-5)


def test_layer_may_be_traced_under_scan(interpret_mode):
    """The applied layer is an operand: one traced kernel serves every
    iteration of a scan over the layers, as the models' ``_layers`` run
    it."""
    packed, padded, w, count, place, group = _case(
        [3, 0, 9, 1, 8, 5], 4, 32, 48, jnp.float32)
    before = counters.snapshot().get("kernels.moe.grouped_mm.pallas", 0)

    @jax.jit
    def every_layer(padded, w, count):
        def body(_, layer):
            return None, moe.grouped_mm(padded, w, count, layer, TILE)
        return jax.lax.scan(body, None, jnp.arange(L, dtype=jnp.int32))[1]

    got = np.asarray(every_layer(padded, w, count))
    assert counters.snapshot()["kernels.moe.grouped_mm.pallas"] == before + 1
    for layer in range(L):
        twin = np.asarray(moe.grouped_mm(packed, w, count, jnp.int32(layer)))
        np.testing.assert_allclose(got[layer][place][group < E],
                                   twin[group < E], atol=1e-5)


def test_the_choice_is_made_from_what_the_code_can_observe():
    """No TPU here: the twin, whatever the widths; under the hook the
    kernel, but never for a dtype it was not written for.  The row tile
    follows the mean rows a group between a sublane tile and 64."""
    assert moe.kernel_mode(2048, 768, jnp.bfloat16) == "off"
    pa._INTERPRET[0] = True
    try:
        assert moe.kernel_mode(2048, 768, jnp.bfloat16) == "pallas"
        assert moe.kernel_mode(16, 8, jnp.float32) == "pallas"
        assert moe.kernel_mode(2048, 768, jnp.float16) == "off"
    finally:
        pa._INTERPRET[0] = False
    assert moe.row_tile(128 * 8, 128, jnp.bfloat16) == 16     # SDAR decode
    assert moe.row_tile(512 * 8, 128, jnp.bfloat16) == 32     # SDAR chunk
    assert moe.row_tile(1024 * 6, 40, jnp.bfloat16) == 64     # DeepSeek chunk
    assert moe.row_tile(32 * 6, 40, jnp.bfloat16) == 16       # DeepSeek decode
    assert moe.row_tile(32 * 6, 40, jnp.float32) == 8
    assert moe._col_tile(2048, 1536, 2) == 1536
    assert moe._col_tile(5120, 3072, 2) == 768
    assert moe._col_tile(1536, 5120, 2) == 2560


# (tokens, choices a token, experts routed over, held (first, count), layers,
#  width, expert width): the expert layers of the two cells' rehearsal models
_REHEARSALS = [
    pytest.param(32, 4, 16, (0, 16), 3, 64, 32, id="sdar-decode"),
    pytest.param(64, 4, 16, (0, 16), 3, 64, 32, id="sdar-chunk"),
    pytest.param(64, 3, 16, (4, 8), 2, 64, 16, id="deepseek-chunk"),
    pytest.param(5, 3, 16, (4, 8), 2, 64, 16, id="deepseek-decode"),
]


@pytest.mark.parametrize("N,k,routed,share,layers,D,F", _REHEARSALS)
def test_held_expert_ffn_with_the_kernel_is_the_twin(N, k, routed, share,
                                                     layers, D, F):
    """The whole layer, kernel on against kernel off: the counts exactly,
    the routed sum to 1e-4 in float32, with rows left out (``live``) and,
    for a share, most pairs held elsewhere."""
    r = np.random.default_rng(N + k)
    first, held = share
    x = jnp.asarray(r.standard_normal((N, D)), jnp.float32)
    gu_w = jnp.asarray(r.standard_normal((layers, held, D, 2 * F))
                       / np.sqrt(D), jnp.float32)
    down_w = jnp.asarray(r.standard_normal((layers, held, F, D))
                         / np.sqrt(F), jnp.float32)
    weight = jnp.asarray(r.uniform(0.1, 1.0, (N, k)), jnp.float32)
    expert = jnp.asarray(np.stack([r.permutation(routed)[:k]
                                   for _ in range(N)]), jnp.int32)
    live = jnp.asarray(r.uniform(size=N) < 0.8)

    def run(layer):
        # a jit of its own a call: the choice is made when it is traced
        return jax.jit(lambda *a: moe.held_expert_ffn(*a, first, layer,
                                                      live))(
            x, weight, expert, gu_w, down_w)

    before = counters.snapshot()
    for layer in range(layers):
        want_y, want_count = run(jnp.int32(layer))
        pa._INTERPRET[0] = True
        try:
            got_y, got_count = run(jnp.int32(layer))
        finally:
            pa._INTERPRET[0] = False
        assert np.array_equal(np.asarray(got_count), np.asarray(want_count))
        np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                                   atol=1e-4)
    grown = counters.delta(before)
    assert grown["kernels.moe.grouped_mm.pallas"] == 2 * layers
    assert grown["kernels.moe.grouped_mm.xla"] == 2 * layers
