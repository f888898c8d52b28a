"""Tensor-parallel serving over the StateArena (paddle_tpu.serving.arena).

The load-bearing contracts: (1) a mesh(1,1) arena is INVISIBLE — engines
key, compile, count and emit bit-identically to unsharded ones; (2) an
mp2 engine is token-identical to single-device for greedy AND seeded
sampling, with the KV pool's head axis actually sharded per chip;
(3) indivisible head counts soft-degrade to replicated
(``serving.mesh.spec_degraded``) instead of failing at compile time;
(4) the arena's LRU'd program cache accounts hits / misses / evictions /
rebuilds truthfully.
"""

import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import counters

PROMPTS = [[5, 9, 11], [7, 3], [5, 9, 2, 4]]
SAMPLE = dict(do_sample=True, temperature=0.9, top_k=8)

# counters whose deltas must match exactly between an unsharded engine
# and a mesh(1,1) arena engine over the same workload (fresh model each,
# so both sides trace cold)
PARITY = ("serving.retraces", "serving.requests", "serving.prefill_batches",
          "serving.decode_steps", "serving.decode_tokens",
          "serving.kv.prefill_chunks", "serving.kv.quant.prefill_tokens",
          "serving.kv.quant.decode_tokens", "serving.spec.drafted",
          "serving.spec.accepted", "serving.spec.verify_steps",
          "kernels.paged.xla_fallbacks", "dist.collective_launches")


def _fresh_model(seed=0, heads=4, hidden=32):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(vocab_size=64, hidden_size=hidden, num_layers=2,
                    num_heads=heads, max_seq_len=32,
                    use_flash_attention=False)
    paddle.seed(seed)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _fresh_draft(seed=1):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(seed)
    d = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=16,
                                 num_layers=1, num_heads=2, max_seq_len=32,
                                 use_flash_attention=False))
    d.eval()
    return d


def _paged(m, **kw):
    from paddle_tpu.serving import LLMEngine
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("min_bucket", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return LLMEngine(m, **kw)


def _run(eng, sampled=False, limit=300):
    hs = [eng.add_request(p, max_new_tokens=5, seed=21 + i,
                          **(SAMPLE if sampled else {}))
          for i, p in enumerate(PROMPTS)]
    n = 0
    while not all(h.is_finished for h in hs):
        eng.step()
        n += 1
        assert n < limit, "engine did not converge"
    return [list(map(int, h.tokens)) for h in hs]


def _mesh(n):
    import jax
    from jax.sharding import Mesh
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices, have {jax.device_count()}")
    return Mesh(np.array(jax.devices()[:n]).reshape(n), ("mp",))


def _measure(build, sampled=False):
    before = counters.snapshot()
    eng = build()
    toks = _run(eng, sampled=sampled)
    delta = counters.delta(before)
    return toks, {k: delta.get(k, 0) for k in PARITY}


# ---------------------------------------------------------------------------
# mesh(1,1): the arena must be invisible
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 invisibility coverage: tag/programs-shared test
def test_mesh1_int8_engine_bit_identical_with_counter_parity():
    mesh = _mesh(1)
    toks, d = _measure(lambda: _paged(_fresh_model(), kv_dtype="int8"),
                       sampled=True)
    toks_m, d_m = _measure(
        lambda: _paged(_fresh_model(), kv_dtype="int8", mesh=mesh),
        sampled=True)
    assert toks == toks_m
    assert d == d_m


@pytest.mark.slow  # four engine builds (two draft/target pairs)
def test_mesh1_speculative_engine_bit_identical_with_counter_parity():
    mesh = _mesh(1)
    toks, d = _measure(
        lambda: _paged(_fresh_model(), draft_model=_fresh_draft(), spec_k=2))
    toks_m, d_m = _measure(
        lambda: _paged(_fresh_model(), draft_model=_fresh_draft(), spec_k=2,
                       mesh=mesh))
    assert toks == toks_m
    assert d == d_m


def test_mesh1_tag_empty_and_programs_shared():
    from paddle_tpu.serving.paged import _model_programs
    mesh = _mesh(1)
    m = _fresh_model()
    e1 = _paged(m)
    _run(e1)
    n_programs = len(_model_programs(m))
    e2 = _paged(m, mesh=mesh)
    assert e2.arena.tag == ""
    _run(e2)
    # mesh(1,1) keys identically: the warm cache served every program
    assert len(_model_programs(m)) == n_programs


# ---------------------------------------------------------------------------
# mp2: token identity + real sharding
# ---------------------------------------------------------------------------

def test_mp2_token_identity_greedy_and_seeded():
    mesh = _mesh(2)
    m = _fresh_model()
    base_g = _run(_paged(m))
    base_s = _run(_paged(m), sampled=True)
    eng = _paged(m, mesh=mesh)
    assert _run(eng) == base_g
    assert _run(_paged(m, mesh=mesh), sampled=True) == base_s
    # the KV pool's head axis is actually sharded per chip
    L, nb, bs, nh, hd = 2, eng.n_blocks, 4, 4, 8
    assert eng.arena.shard_shape("pool_k") == (L, nb, bs, nh // 2, hd)
    assert eng.arena.kv_head_axis
    assert eng.stats()["mesh_tag"] == "[mp2]"


def test_mp2_per_chip_bytes_halve_kv_pool():
    mesh = _mesh(2)
    m = _fresh_model()
    single = _paged(m)
    sharded = _paged(m, mesh=mesh)
    kv1 = single.arena.device_bytes("pool_k", "pool_v")
    kv2 = sharded.arena.device_bytes("pool_k", "pool_v")
    assert kv2 * 2 == kv1
    w1 = single.arena.device_bytes("weights")
    w2 = sharded.arena.device_bytes("weights")
    assert w2 < w1  # matrices shard; norms/biases replicate


@pytest.mark.slow  # tier-1 mp2 coverage: greedy/seeded identity test
def test_mp2_int8_engine_token_identity():
    mesh = _mesh(2)
    m = _fresh_model()
    base = _run(_paged(m, kv_dtype="int8"), sampled=True)
    assert _run(_paged(m, kv_dtype="int8", mesh=mesh), sampled=True) == base


@pytest.mark.slow  # interpret-mode pallas sweep
def test_mp2_pallas_shard_map_token_identity():
    import paddle_tpu.kernels.paged_attention as _pa
    mesh = _mesh(2)
    m = _fresh_model()
    base = _run(_paged(m))
    _pa._INTERPRET[0] = True
    try:
        eng = _paged(m, mesh=mesh)
        assert eng.stats()["kv_kernel"] == "pallas"
        assert _run(eng) == base
        assert eng.arena.kv_head_axis
    finally:
        _pa._INTERPRET[0] = False


def test_mp2_fleet_replicas_construct_mesh_engines():
    from paddle_tpu.serving import ServingFleet
    mesh = _mesh(2)
    m = _fresh_model()
    fleet = ServingFleet(m, replicas=1, max_slots=3, max_seq_len=32,
                         min_bucket=4, block_size=4,
                         prefill_chunk=8, mesh=mesh)
    try:
        rep = fleet._replicas[0]
        assert rep.engine.arena.multi_device
        assert rep.engine.arena.tag == "[mp2]"
        h = fleet.submit(PROMPTS[0], max_new_tokens=4)
        h.wait()
        assert len(h.tokens) > 0
    finally:
        fleet.drain()


# ---------------------------------------------------------------------------
# soft-degrade: indivisible heads
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 degrade coverage: the validate_spec/resolve_spec
# unit tests below exercise both paths
def test_indivisible_heads_degrade_to_replicated_and_stay_identical():
    mesh = _mesh(2)
    m = _fresh_model(seed=3, heads=3, hidden=24)   # nh=3, mp=2
    base = _run(_paged(m))
    before = counters.get("serving.mesh.spec_degraded")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng = _paged(m, mesh=mesh)
    assert counters.get("serving.mesh.spec_degraded") - before >= 2
    assert not eng.arena.kv_head_axis          # head axis replicated
    assert eng.arena.shard_shape("pool_k")[3] == 3
    assert _run(eng) == base


def test_validate_spec_divisible_vs_indivisible():
    from paddle_tpu.distributed.sharding_utils import validate_spec
    from paddle_tpu.serving.arena import KV_POOL_SPEC
    mesh = _mesh(2)
    ticks = []
    ok = validate_spec(KV_POOL_SPEC, (2, 8, 4, 4, 8), mesh,
                       on_fallback=ticks.append)
    assert tuple(ok) == tuple(KV_POOL_SPEC) and not ticks
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        bad = validate_spec(KV_POOL_SPEC, (2, 8, 4, 3, 8), mesh,
                            on_fallback=ticks.append)
    assert tuple(bad) == ()
    assert len(ticks) == 1 and "not divisible" in ticks[0]


def test_arena_degrade_counter_via_resolve_spec():
    from paddle_tpu.serving.arena import KV_POOL_SPEC, StateArena
    mesh = _mesh(2)
    arena = StateArena(mesh=mesh)
    before = counters.get("serving.mesh.spec_degraded")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        spec = arena.resolve_spec("pool_k", KV_POOL_SPEC, (2, 8, 4, 3, 8))
    assert tuple(spec) == ()
    assert counters.get("serving.mesh.spec_degraded") == before + 1


# ---------------------------------------------------------------------------
# arena program cache accounting
# ---------------------------------------------------------------------------

def test_arena_program_cache_lru_eviction_and_rebuild():
    from paddle_tpu.serving.arena import StateArena
    arena = StateArena(program_cache_cap=2)
    store = {}
    built = []

    def builder(key):
        def build():
            built.append(key)
            return f"prog-{key}"
        return build

    before = counters.snapshot()
    assert arena.program(store, "a", builder("a")) == "prog-a"
    assert arena.program(store, "b", builder("b")) == "prog-b"
    assert arena.program(store, "a", builder("a")) == "prog-a"  # hit
    assert arena.program(store, "c", builder("c")) == "prog-c"  # evicts b
    assert "b" not in store
    assert arena.program(store, "b", builder("b")) == "prog-b"  # rebuild
    d = counters.delta(before)
    assert built == ["a", "b", "c", "b"]
    assert d.get("serving.arena.program_hits", 0) == 1
    assert d.get("serving.arena.program_misses", 0) == 4
    assert d.get("serving.arena.program_evictions", 0) >= 1
    assert d.get("serving.arena.program_rebuilds", 0) == 1
    assert counters.get("serving.arena.programs") == 2


def test_arena_passthrough_without_mesh():
    import jax.numpy as jnp
    from paddle_tpu.serving.arena import KV_POOL_SPEC, StateArena
    arena = StateArena()
    v = arena.declare("pool_k", np.zeros((2, 8, 4, 4, 8), np.float32),
                      spec=KV_POOL_SPEC)
    assert isinstance(v, jnp.ndarray)
    assert not arena.kv_head_axis
    assert arena.tag == ""
    assert arena.expected_collectives is None
    tree = {"w": np.ones((4, 4), np.float32)}
    assert arena.declare_tree("weights", tree) is tree
