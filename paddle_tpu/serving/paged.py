"""The serving engine: continuous batching over a paged K/V pool, a
prefix cache and chunked prefill (``serving.LLMEngine``).

A request is charged what it can use, not the worst case.  KV lives in a
shared donated pool ``[L, n_blocks, block_size, nh, hd]`` and each of the
engine's ``max_slots`` rows carries a fixed-shape int32 **block table**
mapping its logical block index to a physical pool block.  What follows:

* **Capacity** — a request reserves only ``ceil((T + max_new - 1)/bs)``
  blocks, so concurrent-user capacity at fixed KV HBM scales with the
  *actual* sequence lengths, not ``S_max`` (vLLM, SOSP '23).
  Reservation is all-or-nothing at admission, so decode can never hit
  mid-flight exhaustion and a refused admission never tears a table.
* **Prefix sharing** — finished sequences donate their blocks to a
  radix tree (``serving.kvcache.PrefixCache``); a prompt that shares a
  cached prefix adopts those blocks read-only instead of re-prefilling
  (RadixAttention).  A shared *partial* block is adopted by
  **copy-on-write**: one compiled copy program clones it into the
  request's private tail block (``serving.kv.cow_copies``), so shared
  blocks are never mutated.  Unreferenced tree blocks are reclaimed LRU
  (``serving.kv.blocks_evicted``) when the pool runs dry.
* **Chunked prefill** — prompts prefill in fixed-size bucketed chunks
  (``prefill_chunk`` knob), one chunk per scheduler step, interleaved
  with the decode launch, so a long prompt can never starve another
  user's inter-token latency.
* **Host-RAM tiering** — with ``host_kv_blocks > 0``, cold blocks spill
  to a pinned host arena instead of vanishing: LRU prefix-tree leaves
  move under device pressure (spill-before-evict) and held requests
  idle past ``spill_idle_steps`` park their private KV host-side until
  migration pages it back.  Spill is one fixed-shape block gather,
  restore one fixed-shape donated scatter — two more programs compiled
  once, zero steady-state retraces — and buffers come from a reuse pool
  so the steady state never mallocs.  The payoff is graceful throughput
  degradation instead of shedding at 2–4× oversubscribed KV.

All device work happens in shape-stable donated XLA programs.  Block
tables ride them as int32 OPERANDS (never shape inputs), so steady state
is O(log prefill_chunk) chunk programs + ONE decode program + one COW
copy program (+ one fixed-shape migration gather/scatter when a
disaggregated fleet hands block tables between replicas) with zero
retraces; the pool is donated through every launch.  Per-row sampling
knobs (temperature / top-k / top-p / greedy) and a per-row PRNG key chain
seeded per request ride the programs as arrays; the sampling math is
``serving.sampling`` — the same transform ``GPT.generate`` traces — and
the key-split schedule replicates ``generate``'s exactly (only the final
chunk's sample is consumed), so engine output is token-identical to
running each request alone through ``generate``.

Those per-slot arrays (token, position, key, knobs, block table, the
``running`` bit) live on the device between launches.  The decode program
masks the rows that are not running itself and returns the next launch's
tokens, positions and keys; the host uploads an array again only after
something else wrote it (``_write_slot``: admission, a last prefill
chunk, a finish, a spill or restore, an adoption).  A launch on which no
slot changed hands makes no host-to-device copy and one read-back, the
tokens (``serving.decode.upload_steps`` counts the others).

That read-back waits one launch: a step enqueues decode launch N+1 from
the device arrays launch N left, and only then reads N's tokens back and
emits them, so the read-back's latency and the host's turn between two
steps run while the device works on N+1.  At most one launch is unread
(``_inflight``); the host mirrors of tokens and positions are those of the
last launch read.  A step reads the launch in flight back FIRST (settles,
``_settle``) when the next launch must upload an operand, since an upload
from the mirrors would roll the other rows back one launch, or when a row
gets its last token from the launch in flight, which must not run once
more; ``serving.decode.overlapped_steps`` counts the launches enqueued
behind an unread one.  Each token stream is what it was; the step that
returns a token is the one after its launch.

The request's own life (queue, deadlines, cancellation, the finish
compare-and-set, ``drain``) is ``serving.engine._RequestLifecycle``,
which this class extends; ``serving.speculative`` extends this one.
"""

from __future__ import annotations

import time
import warnings
import weakref
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..kernels import mla_attention as _mla
from ..kernels import paged_attention as _pa
from ..kernels import window_attention as _wa
from ..profiler import counters
from ..profiler import devicetime as _devicetime
from ..profiler import flight
from ..profiler import metrics
from ..profiler import trace as rtrace
from ..profiler.host_tracer import span
from .arena import StateArena
from .engine import (EngineBackpressure, EngineClosed,
                     LatentCacheUnsupported, RecurrentStateUnsupported,
                     Request, WindowCacheUnsupported, _RequestLifecycle,
                     bucket_length)
from .kvcache import (TRASH_BLOCK, BlockPool, BlockPoolExhausted,
                      HostKVTier, HostTierLost, PrefixCache,
                      blocks_for_tokens)
from .sampling import next_tokens

__all__ = ["LLMEngine"]

# the pool donations are a no-op on CPU backends; the warning would fire
# on every serving step there
warnings.filterwarnings("ignore",
                        message="Some donated buffers were not usable")

# Per-model cache of the jitted serving programs.  The closures capture
# the MODEL only (never an engine), so every engine over the same model
# instance — fleet replicas, respawned replacements — reuses one set of
# XLA executables instead of recompiling identical programs per engine.
# Donation is per-call, and jax.jit keys compiled variants by argument
# shape internally, so sharing is invisible except in compile time (and
# in ``serving.retraces``, which only ever counts FEWER traces).
_MODEL_PROGRAMS = weakref.WeakKeyDictionary()


def _model_programs(model):
    try:
        cache = _MODEL_PROGRAMS.get(model)
        if cache is None:
            cache = _MODEL_PROGRAMS[model] = {}
    except TypeError:  # unhashable / non-weakrefable model object
        cache = model.__dict__.setdefault("_serving_programs", {})
    return cache


# the decode program's per-slot operands, in the order it takes them
# (an adapter engine appends the slab pytree and "aid")
_DECODE_OPERANDS = ("bt", "tok", "pos", "running", "keys", "dosample",
                    "temp", "topk", "topp")


def _mask_idle(running, bt, pos, do_sample, aid=None):
    """What the decode program computes on for a row that is not running
    (idle, mid-prefill, parked for migration): the trash block at
    position 0, no sampling, the base model's adapter row.  The ONE
    decode program runs every launch with fixed shapes, whatever subset
    of rows is live; a parked sampling row cannot send a greedy batch
    down the sampling tail's long branch."""
    return (jnp.where(running[:, None], bt, 0), jnp.where(running, pos, 0),
            do_sample & running,
            None if aid is None else jnp.where(running, aid, 0))


def _sample_and_carry(mesh, logits, running, tok, pos, keys_data, do_sample,
                      temp, top_k, top_p):
    """The end of every decode program: the rows' next tokens (the shared
    sampling tail), and with them the next launch's own operands as this
    launch leaves them: a running row's new token, position and key;
    every other row's as they came in.  Replicated on the arena's mesh,
    like the uploads they stand in for."""
    nxt, new_keys = next_tokens(logits, keys_data, do_sample, temp, top_k,
                                top_p)
    out = (jnp.where(running, nxt, tok), pos + running.astype(pos.dtype),
           jnp.where(running[:, None], new_keys, keys_data))
    if mesh is not None:
        rep = NamedSharding(mesh, PartitionSpec())
        out = tuple(jax.lax.with_sharding_constraint(x, rep) for x in out)
    return out


class _Launch:
    """A decode launch the host has not read back: the rows it ran
    (``(slot, request)`` as the host saw them at the dispatch), its tokens,
    what it carries to the next launch (``tok``, ``pos``, ``keys``), the
    instant its operands began, and the engine's launch count after it."""

    __slots__ = ("rows", "nxt", "carried", "t0", "seq")

    def __init__(self, rows, nxt, carried, t0, seq):
        self.rows, self.nxt, self.carried = rows, nxt, carried
        self.t0, self.seq = t0, seq


class LLMEngine(_RequestLifecycle):
    """Continuous-batching engine over one causal LM (``GPTForCausalLM``,
    ``OlmoHybridForCausalLM``, ``DeepseekV2ForCausalLM``,
    ``SdarMoeForCausalLM``: anything with ``cache_spec()``,
    ``decode_state()``, ``prefill_paged`` and ``decode_paged``).  A model
    whose ``cache_spec()`` names a ``decode_block`` (generation by
    diffusion over blocks: a launch is one denoising pass of every running
    row's block) is served by the subclass in ``serving.block_decode``,
    which this constructor returns for it.

    ``add_request()`` enqueues (bounded queue, optional blocking
    backpressure); ``step()`` admits into free slots by reserving K/V
    blocks, advances every mid-prefill request by one chunk, runs one
    decode launch for every running slot, and evicts finished rows;
    ``generate()`` is the blocking convenience loop; iterating a returned
    ``Request`` streams its tokens.  ``drain()`` stops admission and
    finishes all outstanding work.

    Cache knobs:

    * ``block_size`` — tokens per KV block (default 16).
    * ``n_blocks`` — physical pool blocks *including* the reserved trash
      block 0; default ``max_slots * ceil(S_max/bs) + 1`` (every slot can
      hold a sequence of ``S_max``).
    * ``prefill_chunk`` — max tokens prefilled per scheduler step
      (default ``min(S_max, 128)``); chunk programs are bucketed
      powers-of-two from ``min_bucket`` up to this.
    * ``prefix_cache`` — enable the COW prefix tree (default True).
    * ``host_kv_blocks`` — host-RAM tier capacity in blocks (default 0:
      tier disabled).  Requires the prefix cache.
    * ``spill_idle_steps`` — scheduler steps a held request sits idle
      before its private KV spills to the host tier (default 0: held
      requests never spill).

    A model with window layers (``cache_spec()["window"]``) gets a second
    pool for them of ``max_slots * window_entries + 1`` blocks: each slot
    owns ``window_entries = ceil((W + prefill_chunk) / bs) + 1`` of them,
    which its row reuses as a ring as it advances past the window.
    """

    def __new__(cls, *args, **kw):
        # a draft_model= routes construction to the speculative subclass,
        # so `LLMEngine(model, draft_model=...)` is the one public spelling
        # and a model that decodes by blocks to the block-decoding one
        # (both import this module; resolve lazily)
        if cls is LLMEngine:
            model = args[0] if args else kw.get("model")
            spec = getattr(model, "cache_spec", None)
            if spec is not None and spec().get("decode_block"):
                from .block_decode import BlockDecodeLLMEngine
                return super().__new__(BlockDecodeLLMEngine)
            if kw.get("draft_model") is not None:
                from .speculative import SpeculativeLLMEngine
                return super().__new__(SpeculativeLLMEngine)
        return super().__new__(cls)

    def __init__(self, model, max_slots=8, max_seq_len=None, queue_size=64,
                 min_bucket=8, eos_token_id=None, kv_layout="paged",
                 block_size=16, n_blocks=None, prefill_chunk=None,
                 prefix_cache=True, kv_dtype=None, weight_dtype=None,
                 host_kv_blocks=0, spill_idle_steps=0, mesh=None,
                 shard_rules=None, adapter_slots=0, adapter_rank=8,
                 tenant_buckets=8):
        # not an option: there is one layout.  The keyword is still taken
        # because benchmark/workloads/*.json pass it in their `engine`
        # blocks, benchmark/kinds/serve_open_loop*.py forward them as
        # **kw, and PR 30 could edit nothing under benchmark/.  ROADMAP
        # D16: the next `benchmark` issue drops the key there, then this
        # parameter and the check go.
        if kv_layout != "paged":
            raise ValueError(
                f"kv_layout={kv_layout!r}: the slot layout was removed in "
                "PR 30; the engine is paged (drop the keyword)")
        if kv_dtype not in (None, "int8", "fp8"):
            raise ValueError(f"kv_dtype must be None, 'int8' or 'fp8', "
                             f"got {kv_dtype!r}")
        if weight_dtype not in (None, "int8"):
            raise ValueError(f"weight_dtype must be None or 'int8', "
                             f"got {weight_dtype!r}")
        c = model.config
        # what the model caches: paged K/V for ``kv_layers`` layers (per
        # head, or with ``kv_row`` one latent row per token and no head
        # axis), one row per slot of each ``slot_state`` array (recurrent
        # layers), and the ``step_state`` arrays that every program takes
        # and hands on (an expert model's load counts)
        cache = model.cache_spec()
        self.slot_state = dict(cache["slot_state"])
        self._step_spec = dict(cache.get("step_state", {}))
        self.kv_row = int(cache.get("kv_row", 0))
        # the ``[k ; v]`` row is read by the banded walk
        # (``kernels/window_attention.py``) even with no window
        self._banded = bool(cache.get("banded_walk"))
        # window layers: their rows go to a second pool, a ring a row
        self.window = dict(cache.get("window") or {})
        if self.slot_state or self.kv_row or self.window:
            asked = {"kv_dtype=": kv_dtype is not None,
                     "host_kv_blocks=": int(host_kv_blocks or 0) > 0,
                     "adapter_slots=": int(adapter_slots or 0) > 0,
                     "mesh=": mesh is not None}
            if any(asked.values()):
                refusal, what = (
                    (WindowCacheUnsupported,
                     "keeps its window layers' K/V in a ring of blocks of "
                     "a second pool") if self.window
                    else (RecurrentStateUnsupported,
                          "keeps recurrent state per request")
                    if self.slot_state
                    else (LatentCacheUnsupported,
                          "caches one latent row per token, with no head "
                          "axis"))
                raise refusal(
                    f"{type(model).__name__} {what}, which "
                    + ", ".join(k for k, v in asked.items() if v)
                    + " cannot carry yet")
            # a prefix hit adopts K/V blocks and would skip the tokens
            # that built the recurrent state; its copy-on-write clone
            # copies a block by head, which a latent row has not; and it
            # adopts blocks of one pool, where window layers keep a second
            prefix_cache = False
        S = int(max_seq_len or c.max_seq_len)
        if not c.use_rope and S > c.max_seq_len:
            raise ValueError(
                f"max_seq_len {S} exceeds the model's "
                f"learned-position table ({c.max_seq_len})")
        super().__init__(max_slots, S, queue_size, eos_token_id,
                         adapter_slots, tenant_buckets)
        self.model = model
        self.config = c
        self.kv_layers = int(cache["kv_layers"])
        self.adapter_rank = int(adapter_rank)   # LoRA (adapter_slots > 0)
        self.block_size = int(block_size)
        self.n_blocks = n_blocks
        self.prefill_chunk = prefill_chunk
        self.prefix_caching = bool(prefix_cache)
        self.kv_dtype = kv_dtype
        self.weight_dtype = weight_dtype
        # host-RAM KV tier knobs (0 disables)
        self.host_kv_blocks = int(host_kv_blocks or 0)
        self.spill_idle_steps = int(spill_idle_steps or 0)
        self.min_bucket = int(min_bucket)
        self._captured = set()    # program names already sent to telemetry
        # the arena owns every declared device-resident leaf (weights, KV
        # pools, scale pools) with resolved NamedSharding specs; with
        # mesh=None it is a bit-identical pass-through
        with span("serving.engine_init", level=0):
            self.arena = StateArena(mesh=mesh, shard_rules=shard_rules)
            if weight_dtype == "int8":
                from ..quantization import ptq_int8_decode_state
                self._w = self.arena.declare_tree(
                    "weights", ptq_int8_decode_state(model))
            else:
                self._w = self.arena.declare_tree(
                    "weights", model.decode_state())
            self._init_kv(c, self.max_slots, S, int(cache["kv_heads"]),
                          int(cache["head_dim"]), jnp.dtype(c.dtype))
        self.hists["serving.kv.block_occupancy"] = metrics.Histogram(
            "serving.kv.block_occupancy", "frac")

    def _init_kv(self, c, B, S, nh, hd, dt):
        bs = self.block_size
        self.window_entries, self._wblock_bytes = 0, 0
        if not 1 <= bs <= S:
            raise ValueError(f"block_size {bs} outside [1, {S}]")
        self.max_blocks = blocks_for_tokens(S, bs)
        if self.n_blocks is None:
            self.n_blocks = B * self.max_blocks + 1
        self.n_blocks = int(self.n_blocks)
        if self.prefill_chunk is None:
            self.prefill_chunk = min(S, 128)
        self.prefill_chunk = max(int(self.prefill_chunk), self.min_bucket)
        self.pool = BlockPool(self.n_blocks, bs, kv_dtype=self.kv_dtype)
        self.prefix = PrefixCache(self.pool) if self.prefix_caching else None
        adt = _pa.KV_DTYPES[self.kv_dtype] if self.kv_dtype else dt
        from .arena import KV_POOL_SPEC
        L = self.kv_layers
        if self.kv_row:
            # a latent cache: ONE pool of rows stored as whole lane tiles
            # (576 values as 640), replicated (nothing to split); the
            # programs take and hand on a second pool that is None
            nhp, hd = 1, _mla.pool_row(self.kv_row)
            self.arena.declare(
                "pool_k", jnp.zeros((L, self.n_blocks, bs, hd), adt))
            self._block_bytes = L * bs * hd * jnp.dtype(adt).itemsize
            self._init_window(B, bs, hd, adt)
        else:
            # one chip's pool stores whole (8, 128) tiles of heads where
            # that lets the block-table walk run (30 heads of 128 as 32);
            # a pool whose head axis shards over a mesh keeps the model's
            # own count
            nhp = (nh if self.arena.mesh is not None
                   else _pa.pool_heads(nh, hd))
            self.arena.declare(
                "pool_k", jnp.zeros((L, self.n_blocks, bs, nhp, hd), adt),
                spec=KV_POOL_SPEC)
            self.arena.declare(
                "pool_v", jnp.zeros((L, self.n_blocks, bs, nhp, hd), adt),
                spec=KV_POOL_SPEC)
            self._block_bytes = (2 * L * bs * nhp * hd
                                 * jnp.dtype(adt).itemsize)
        # recurrent layers: one row per slot of each array the model
        # names, next to the pools and donated through the same programs
        slot_names = tuple(sorted(self.slot_state))
        for name in slot_names:
            lead, per_slot, sdt = self.slot_state[name]
            self.arena.declare(
                "state." + name,
                jnp.zeros(tuple(lead) + (B,) + tuple(per_slot), sdt))
        # fixed at construction, whatever the requests' lengths
        self._state_bytes = self.arena.device_bytes(
            *("state." + n for n in slot_names))
        # what the programs carry forward beside the pools: the rows'
        # recurrent state and the model's own running counts
        for name, (shape, sdt) in self._step_spec.items():
            self.arena.declare("state." + name,
                               jnp.zeros(tuple(shape), sdt))
        self._state_names = slot_names + tuple(sorted(self._step_spec))
        if self.kv_dtype:
            # per-token fp32 scales at the same (layer, block, position)
            # address as the quantized tiles (donated alongside them);
            # no head axis, so they stay replicated on a mesh
            self.arena.declare(
                "scale_k",
                jnp.zeros((L, self.n_blocks, bs), jnp.float32))
            self.arena.declare(
                "scale_v",
                jnp.zeros((L, self.n_blocks, bs), jnp.float32))
            tile = L * self.n_blocks * bs * nhp * hd
            raw = 2 * tile * jnp.dtype(dt).itemsize
            quant = (2 * tile * jnp.dtype(adt).itemsize
                     + 2 * L * self.n_blocks * bs * 4)
            counters.set_gauge("serving.kv.quant.arena_bytes", quant)
            counters.set_gauge("serving.kv.quant.bytes_saved",
                               max(raw - quant, 0))
        else:
            self.arena.declare("scale_k", None)
            self.arena.declare("scale_v", None)
        # which attention the decode program compiles with — resolved
        # ONCE at construction from the platform and the heads a chip
        # holds, and baked into the program-cache key.  Pools left
        # replicated on a mesh (indivisible heads) keep the twin: GSPMD
        # partitions it, and cannot partition a Mosaic call
        if self.window or self._banded:
            self.kv_kernel = _wa.kernel_mode(c.head_dim, hd)
        elif self.kv_row:
            self.kv_kernel = _mla.kernel_mode(c.num_heads, hd)
        elif self.arena.kv_head_axis:
            self.kv_kernel = _pa.kernel_mode(
                nh // self.arena.mesh.shape["mp"], hd)
        elif self.arena.multi_device:
            self.kv_kernel = "off"
        else:
            self.kv_kernel = _pa.kernel_mode(nhp, hd)
        if self.kv_kernel == "pallas":
            _pa.preload()
        # The per-slot decode state.  Each array lives twice: a host
        # mirror (what the scheduler, the spill and the migration read)
        # and, in ``_dev``, the device copy that the decode launch takes.
        # The decode program carries tokens, positions and keys forward
        # itself; whatever else writes a row goes through _write_slot,
        # which names the array in ``_stale`` so that the next launch
        # uploads it again.  A launch with nothing stale uploads nothing.
        key_size = jax.random.key_data(jax.random.key(0)).shape[0]
        self._bt = np.zeros((B, self.max_blocks + self.window_entries),
                            np.int32)
        self._tok = np.zeros(B, np.int32)
        self._pos = np.zeros(B, np.int32)
        self._running = np.zeros(B, np.bool_)
        self._keys_host = np.zeros((B, key_size), np.uint32)
        self._dosample = np.zeros(B, np.bool_)
        self._temp = np.ones(B, np.float32)
        self._topk = np.zeros(B, np.int32)
        self._topp = np.ones(B, np.float32)
        # adapter arena row per slot (row 0 = base model)
        self._aid = np.zeros(B, np.int32)
        self._dev = {}
        self._stale = set(_DECODE_OPERANDS)
        # the decode launch not yet read back (a _Launch), the instant of
        # the last read-back, and the list the last step() returned (a
        # read-back between steps adds its tokens' events there)
        self._inflight = None
        self._read_ns = 0
        self._events = []
        self._slot_blocks = [None] * B
        self._prefill_state = {}      # slot -> {"req": Request, "done": n}
        # perf_counter_ns at which a read-back left the device with nothing
        # the engine queued; 0 once a launch took it (or nobody profiles);
        # launches the engine made, to tell whether one is queued behind a
        # read-back
        self._drained_ns = 0
        self._launches = 0
        self._pchunk_jits = {}        # chunk bucket -> jitted prefill
        self._pdecode_jit = None
        self._pcopy_jit = None
        self._pmigrate_jit = None
        self._pspill_jit = None
        self._prestore_jit = None
        # host-RAM KV tier: cold prefix leaves and idle held requests
        # spill their blocks into pinned host buffers and page back on
        # demand (requires the prefix tree — its nodes key the entries)
        self._host_tier = (HostKVTier(self.host_kv_blocks)
                           if self.host_kv_blocks > 0
                           and self.prefix is not None else None)
        if self.prefix is not None:
            self.prefix.tier = self._host_tier
        # one host buffer spec per block: K/V tiles (+ scale rows)
        spec = [((L, bs, nhp, hd), np.dtype(adt))] * 2
        if self.kv_dtype:
            spec += [((L, bs), np.dtype(np.float32))] * 2
        self._host_spec = tuple(spec)
        self._req_host = {}    # rid -> {"idx": set[int], "lost": bool}
        self._held_idle = {}   # rid -> idle scheduler steps while held
        # multi-tenant LoRA adapter arena (adapter_slots=0 disables; the
        # slabs are declared through the same StateArena as the KV pools
        # so they inherit the donation/compile-cache protocol)
        if self.adapter_slots > 0:
            from .adapters import AdapterArena
            self.adapters = AdapterArena(
                self.model, self.arena, _model_programs(self.model),
                self.adapter_slots, self.adapter_rank,
                dispatch=self._dispatch)
        else:
            self.adapters = None
        # the decode program's per-slot operands, in its order
        self._operand_names = _DECODE_OPERANDS + (
            ("aid",) if self.adapters is not None else ())
        # per-engine prefix-cache accounting (the fleet sums these; the
        # same events also feed the process-global counters registry)
        self.kv_prefix_hits = 0
        self.kv_prefix_misses = 0
        self.kv_prefix_hit_tokens = 0
        self.kv_cow_copies = 0
        self.kv_blocks_evicted = 0
        self.kv_pool_exhausted_events = 0
        self.kv_tier_spilled = 0
        self.kv_tier_restored = 0

    def _init_window(self, B, bs, row, adt):
        """The window layers' pool (``pool_v`` of a row cache, which has
        no second pool otherwise) and each slot's ring in it: a chunk
        reads keys from ``W - 1`` before its first position to its last,
        so ``ceil((W + prefill_chunk) / bs) + 1`` entries hold every key
        still needed while the row writes the next block into the entry
        of the block ``window_entries`` behind it.  Slot ``s`` owns blocks
        ``1 + s * n .. (s + 1) * n`` for good: no request ever waits for
        the window pool, and nothing is allocated or freed in it."""
        if not self.window:
            self.arena.declare("pool_v", None)
            return
        n = self.window_entries = blocks_for_tokens(
            self.window["size"] + self.prefill_chunk, bs) + 1
        self._ring = (TRASH_BLOCK + 1
                      + np.arange(B * n, dtype=np.int32).reshape(B, n))
        Lw = int(self.window["layers"])
        # every slot's whole ring, and the trash block
        self.arena.declare("pool_v",
                           jnp.zeros((Lw, B * n + 1, bs, row), adt))
        self._wblock_bytes = Lw * bs * row * jnp.dtype(adt).itemsize

    # the block pools (+ scale pools) live in the StateArena; the
    # donated-program outputs rebind through the setters, so every
    # dispatch site — chunk prefill, decode, COW, migration,
    # spill/restore — inherits the resolved sharding without re-proving
    # donation safety
    @property
    def _pk(self):
        return self.arena.get("pool_k")

    @_pk.setter
    def _pk(self, v):
        self.arena.bind("pool_k", v)

    @property
    def _pv(self):
        return self.arena.get("pool_v")

    @_pv.setter
    def _pv(self, v):
        self.arena.bind("pool_v", v)

    @property
    def _sk(self):
        return self.arena.get("scale_k")

    @_sk.setter
    def _sk(self, v):
        self.arena.bind("scale_k", v)

    @property
    def _sv(self):
        return self.arena.get("scale_v")

    @_sv.setter
    def _sv(self, v):
        self.arena.bind("scale_v", v)

    @property
    def _st(self):
        """The per-slot state arrays of a model with recurrent layers,
        ``{name: array}`` as the model's programs take them."""
        return {n: self.arena.get("state." + n) for n in self._state_names}

    @_st.setter
    def _st(self, v):
        for n in self._state_names:
            self.arena.bind("state." + n, None if v is None else v[n])

    # -- the per-slot decode state ---------------------------------------
    def _write_slot(self, where, **rows):
        """The one writer of a slot's decode state from outside the
        decode program: ``rows`` names the arrays (``tok``, ``pos``,
        ``keys``, ``bt``, ``running``, ``dosample``, ``temp``, ``topk``,
        ``topp``, ``aid``) and ``where`` indexes them (a slot, or
        ``(slot, i)`` for one entry of a table).  The host mirror takes
        the value and the array is marked stale, so the next decode
        launch uploads it again.  Caller holds ``_cond`` where another
        thread may step the engine."""
        for name, value in rows.items():
            getattr(self, "_" + name)[where] = value
        self._stale.update(rows)

    @property
    def _keys(self):
        """Host copy of the rows' key data.  The decode program carries
        the chain on the device, so the copy is fetched when someone
        asks (a last prefill chunk, ``export_request``, the speculative
        round), not once a launch."""
        with self._cond:
            if self._keys_host is None:
                self._keys_host = np.array(self._dev["keys"])
            return self._keys_host

    @_keys.setter
    def _keys(self, value):
        with self._cond:
            self._keys_host = value
            self._stale.add("keys")

    def _decode_operands(self):
        """The decode launch's per-slot operands, in the program's
        order, and whether any had to be uploaded: only the arrays that
        _write_slot marked since the last launch are (the caller has read
        any launch in flight back first); the rest are the device arrays
        of the launch before, and what a launch in flight carries is its
        own outputs."""
        names = self._operand_names
        with self._cond:
            stale, self._stale = self._stale.intersection(names), set()
            # copies: a backend may alias a host array it is handed
            fresh = {n: np.array(getattr(self, "_" + n)) for n in stale}
        for n, value in fresh.items():
            self._dev[n] = self.arena.operand(value)
        dev = self._dev
        if self._inflight is not None:
            dev = {**dev, **self._inflight.carried}
        ops = [dev[n] for n in names]
        if self.adapters is not None:
            ops.insert(-1, self.adapters.slabs())
        return tuple(ops), bool(fresh)

    def release_kv(self):
        """Drop the device KV storage (a dead replica's arena is garbage
        — the fleet frees its HBM before respawning), and the launch in
        flight with it.  The requests those two hold reference the engine,
        so they go too: refcounting alone then frees an engine dropped
        after this."""
        self._pk = self._pv = self._sk = self._sv = self._st = None
        self._inflight, self._events = None, []
        if self.adapters is not None:
            self.adapters.release_slabs()

    def _dispatch(self, name, fn, args, dn):
        """``fn(*args)`` inside the capture/audit/devicetime bracket every
        engine dispatch gets.  The adapter arena's load program takes it as
        a callback, so it never reaches into engine internals; a subclass
        with programs of its own launches them through it."""
        self._maybe_capture(name, fn, *args)
        self._maybe_audit(name, fn, *args, donate_argnums=dn)
        _dt = _devicetime.note(name)
        out = fn(*args)
        _devicetime.observe(_dt, out)
        return out

    def _drained(self, sp, t_ns=None):
        """A blocking read-back under the wait span ``sp`` returned and
        the device holds nothing the engine queued (the caller knows: a
        read-back with a launch queued behind it is no drain).  Stamped
        only for someone who profiles, and only the first time since the
        last launch."""
        if sp.live and not self._drained_ns:
            self._drained_ns = t_ns or time.perf_counter_ns()

    def _launch_span(self, name):
        """The ``serving.*.dispatch`` span of a launch.  The first launch
        after the device drained opens it with ``gap_ns``, the host time
        since the drained stamp, in which the device had nothing to run."""
        self._launches += 1
        if not self._drained_ns:
            return span(name)
        gap = time.perf_counter_ns() - self._drained_ns
        self._drained_ns = 0
        return span(name, gap_ns=gap)

    def register_adapter(self, tenant, factors):
        """Stage ``tenant``'s LoRA factors host-side (see
        :meth:`AdapterArena.register`); they page into the device arena
        on the tenant's first admission."""
        if self.adapters is None:
            raise ValueError("engine was built with adapter_slots=0")
        with self._cond:
            self.adapters.register(tenant, factors)

    def adapter_peek(self, tenant):
        """Tokens of prefill-equivalent work saved because ``tenant``'s
        LoRA factors are already resident in this replica's adapter
        arena (0 without adapters).  The Router folds this into the same
        cost model as ``prefix_peek`` for tenant-affine dispatch."""
        if self.adapters is None or tenant is None:
            return 0
        with self._cond:
            return self.adapters.peek(tenant)

    @staticmethod
    def _prefix_key(tokens, tenant):
        """Tenant-salted token stream for the prefix tree.  KV computed
        under a LoRA adapter is NOT interchangeable with base-model KV
        for the same tokens (the adapter perturbs the QKV projection),
        so each tenant's cached prefixes live in a disjoint key plane:
        tokens are offset by a per-tenant constant above the vocab range
        (block alignment preserved, base traffic stays unsalted — its
        tree behavior is bit-identical to the adapter-free engine)."""
        if tenant is None:
            return tokens
        salt = (zlib.crc32(str(tenant).encode("utf-8")) + 1) << 32
        return [t + salt for t in tokens]

    def prefix_peek(self, prompt, tenant=None):
        """Tokens of ``prompt`` the prefix cache could serve without
        prefilling (0 with the cache off).  ``tenant`` scopes the probe
        to that adapter's KV plane (see :meth:`_prefix_key`)."""
        if self.prefix is None:
            return 0
        ids = np.asarray(
            prompt._data if hasattr(prompt, "_data") else prompt,
            dtype=np.int32).reshape(-1)
        with self._cond:
            return self.prefix.peek(
                self._prefix_key(ids.tolist(), tenant),
                int(ids.shape[0]) - 1)

    def prefix_probe(self, prompt, tenant=None):
        """``(device_tokens, host_tokens)`` the prefix cache could serve
        for this prompt — the router's restore-aware dispatch score
        (device hits are free; host hits pay a page-in first, so the
        cost model discounts them).  Cheap on misses: the radix digest
        short-circuits the walk (see ``PrefixCache.probe``).  ``tenant``
        scopes the probe to that adapter's KV plane (see
        :meth:`_prefix_key`)."""
        if self.prefix is None:
            return 0, 0
        ids = np.asarray(
            prompt._data if hasattr(prompt, "_data") else prompt,
            dtype=np.int32).reshape(-1)
        with self._cond:
            return self.prefix.probe(
                self._prefix_key(ids.tolist(), tenant),
                int(ids.shape[0]) - 1)

    # -- compiled programs ---------------------------------------------------
    def _maybe_capture(self, name, fn, *args):
        """Record HBM/compile/FLOPs stats for a compiled program, once per
        program name (gated by FLAGS_device_telemetry; the AOT lower costs
        a second trace, so the serving.retraces warm-path invariant only
        holds with telemetry off)."""
        if metrics.device_telemetry_enabled() and name not in self._captured:
            self._captured.add(name)
            metrics.capture_program_stats(name, fn, *args)

    def _maybe_audit(self, name, fn, *args, donate_argnums=()):
        """AOT-audit a compiled program once per name under
        FLAGS_program_audit (donation aliasing, host callbacks, static
        shapes, collective census — see analysis/program_audit).  Like
        ``_maybe_capture``, the audit's extra AOT trace bumps
        ``serving.retraces`` once per program, at the compile/warmup site
        only — steady-state windows see a no-op set lookup."""
        from ..analysis import program_audit as _audit
        expected = self.arena.expected_collectives
        if expected is not None:
            # multi-device arena: in-graph collectives (GSPMD's TP
            # reductions) are expected; anything else still fails
            _audit.maybe_audit(name, fn, *args,
                               donate_argnums=donate_argnums,
                               expected_collectives=expected)
        else:
            _audit.maybe_audit(name, fn, *args,
                               donate_argnums=donate_argnums,
                               expect_no_collectives=True)

    @staticmethod
    def _first_token(logits, key_data, do_sample, temp, top_k, top_p):
        """The prefill's first token from ``logits[1, V]``: the shared
        sampling tail over a batch of one (identical key discipline and
        math to generate's post-prefill draw)."""
        nxt, new_keys = next_tokens(
            logits, key_data[None],
            *(jnp.reshape(x, (1,)) for x in (do_sample, temp, top_k, top_p)))
        return nxt[0], new_keys[0]

    # The jitted callables live in the per-model cache shared by every
    # engine over the same model (see _model_programs above): the
    # closures capture the MODEL only, and jax.jit keys compiled variants
    # by argument shape, so chunk buckets and differing pool sizes each
    # get their own executable while identical engines reuse them.
    # Engines whose attention backend or KV precision differ get distinct
    # cache keys (``_prog_key``) — a program traced for one attention
    # backend / kv_dtype must never serve another.
    # The arena tag (e.g. "[mp2]") rides the key AND the display name so
    # a sharded program can never serve an unsharded engine, and ledger /
    # capture rows stay distinguishable per mesh shape.
    # An adapter-enabled engine's programs take two extra operands (the
    # slab pytree + per-row ids), so they key separately — an
    # adapter-free engine keys exactly as before and shares nothing with
    # an adapter engine over the same model.
    def _prog_key(self, base):
        lo = (f"+lora{self.adapter_rank}"
              if getattr(self, "adapters", None) is not None else "")
        if self.kv_kernel == "off" and self.kv_dtype is None:
            return base + lo + self.arena.tag
        return (f"{base}@{self.kv_kernel}:{self.kv_dtype or 'raw'}"
                f"{lo}{self.arena.tag}")

    def _build_pchunk(self):
        """The jitted chunk program (one per engine, every bucket a
        shape of it); a subclass with another chunk overrides this."""
        model = self.model
        mode = self.kv_kernel
        # adapter engines append the slab pytree + per-row ids as
        # trailing operands (never donated — the gather reads
        # them); donation indices are untouched
        lora = self.adapters is not None
        # a model with window layers is told how many entries of each
        # row's table are its ring
        wkw = ({"window_entries": self.window_entries} if self.window
               else {})

        if self._state_names:
            def pchunk(w, ids, start, length, bt, pk, pv, st, slot,
                       key_data, do_sample, temp, top_k, top_p):
                counters.inc("serving.retraces")  # trace-time only
                pk, pv, st, logits = model.prefill_paged(
                    w, ids, start, length, bt, pk, pv, st, slot,
                    kernel=mode, **wkw)
                tok, new_key = LLMEngine._first_token(
                    logits, key_data, do_sample, temp, top_k, top_p)
                return pk, pv, st, tok, new_key
            return jax.jit(pchunk, donate_argnums=(5, 6, 7))

        if self.kv_dtype:
            def pchunk(w, ids, start, length, bt, pk, pv, sk, sv,
                       key_data, do_sample, temp, top_k, top_p,
                       *ad):
                counters.inc("serving.retraces")  # trace-time only
                aw, aid = ad if lora else (None, None)
                pk, pv, sk, sv, logits = model.prefill_paged(
                    w, ids, start, length, bt, pk, pv, sk, sv,
                    adapters=aw, adapter_ids=aid)
                tok, new_key = LLMEngine._first_token(
                    logits, key_data, do_sample, temp, top_k, top_p)
                return pk, pv, sk, sv, tok, new_key
            return jax.jit(pchunk, donate_argnums=(5, 6, 7, 8))

        def pchunk(w, ids, start, length, bt, pk, pv, key_data,
                   do_sample, temp, top_k, top_p, *ad):
            counters.inc("serving.retraces")  # trace-time only
            aw, aid = ad if lora else (None, None)
            pk, pv, logits = model.prefill_paged(
                w, ids, start, length, bt, pk, pv,
                adapters=aw, adapter_ids=aid)
            tok, new_key = LLMEngine._first_token(
                logits, key_data, do_sample, temp, top_k, top_p)
            return pk, pv, tok, new_key
        return jax.jit(pchunk, donate_argnums=(5, 6))

    def _pchunk_for(self, bucket):
        fn = self._pchunk_jits.get(bucket)
        if fn is None:
            key = self._prog_key("prefill_paged")
            with span("serving.program_build", level=0, key=key,
                      bucket=bucket):
                fn = self.arena.program(_model_programs(self.model), key,
                                        self._build_pchunk)
            self._pchunk_jits[bucket] = fn
            counters.set_gauge("serving.prefill_programs",
                               len(self._pchunk_jits))
        return fn

    def _build_pdecode(self):
        """The jitted decode program; a subclass with another decode
        launch overrides this."""
        model = self.model
        mode = self.kv_kernel
        # the pallas kernel is per-head independent, so under a mesh
        # whose KV head axis actually sharded it runs through a
        # shard_map over "mp" (see kernels.paged_attention); the
        # gather twin needs nothing — GSPMD partitions it from the
        # committed input shardings alone
        mesh = (self.arena.mesh
                if mode == "pallas" and self.arena.kv_head_axis
                else None)
        head_axis = "mp" if mesh is not None else None

        # the carried operands stay replicated on the arena's mesh,
        # as arena.operand uploads them
        rep = self.arena.mesh
        lora = self.adapters is not None
        wkw = ({"window_entries": self.window_entries} if self.window
               else {})

        # every variant takes the rows' UNMASKED state and masks
        # it itself, and returns, after the tokens and the pools,
        # the positions and keys of the next launch; the tokens it
        # returns are the next launch's too
        if self._state_names:
            def decode(w, pk, pv, st, bt, tok, pos, running,
                       keys_data, do_sample, temp, top_k, top_p):
                counters.inc("serving.retraces")
                bt_e, pos_e, ds_e, _ = _mask_idle(
                    running, bt, pos, do_sample)
                logits, pk, pv, st = model.decode_paged(
                    w, tok, pos_e, bt_e, pk, pv, st, running,
                    kernel=mode, **wkw)
                nxt, pos, keys_data = _sample_and_carry(
                    rep, logits, running, tok, pos, keys_data, ds_e,
                    temp, top_k, top_p)
                return nxt, pk, pv, st, pos, keys_data
            return jax.jit(decode, donate_argnums=(1, 2, 3))

        if self.kv_dtype:
            def decode(w, pk, pv, sk, sv, bt, tok, pos, running,
                       keys_data, do_sample, temp, top_k, top_p,
                       *ad):
                counters.inc("serving.retraces")
                aw, aid = ad if lora else (None, None)
                bt_e, pos_e, ds_e, aid = _mask_idle(
                    running, bt, pos, do_sample, aid)
                logits, pk, pv, sk, sv = model.decode_paged(
                    w, tok, pos_e, bt_e, pk, pv, sk, sv,
                    kernel=mode, mesh=mesh, head_axis=head_axis,
                    adapters=aw, adapter_ids=aid)
                nxt, pos, keys_data = _sample_and_carry(
                    rep, logits, running, tok, pos, keys_data, ds_e,
                    temp, top_k, top_p)
                return nxt, pk, pv, sk, sv, pos, keys_data
            return jax.jit(decode, donate_argnums=(1, 2, 3, 4))

        def decode(w, pk, pv, bt, tok, pos, running, keys_data,
                   do_sample, temp, top_k, top_p, *ad):
            counters.inc("serving.retraces")
            aw, aid = ad if lora else (None, None)
            bt_e, pos_e, ds_e, aid = _mask_idle(
                running, bt, pos, do_sample, aid)
            logits, pk, pv = model.decode_paged(
                w, tok, pos_e, bt_e, pk, pv, kernel=mode,
                mesh=mesh, head_axis=head_axis,
                adapters=aw, adapter_ids=aid)
            nxt, pos, keys_data = _sample_and_carry(
                rep, logits, running, tok, pos, keys_data, ds_e,
                temp, top_k, top_p)
            return nxt, pk, pv, pos, keys_data
        return jax.jit(decode, donate_argnums=(1, 2))

    def _pdecode(self):
        if self._pdecode_jit is None:
            key = self._prog_key("decode_paged")
            with span("serving.program_build", level=0, key=key):
                self._pdecode_jit = self.arena.program(
                    _model_programs(self.model), key, self._build_pdecode)
        return self._pdecode_jit

    def _pcopy(self):
        """Copy-on-write block clone: ``dst[:nvalid] = src[:nvalid]``,
        zero beyond (one fixed-shape donated program; the quantized
        variant clones the per-token scale rows alongside the tiles)."""
        if self._pcopy_jit is None:
            def build():
                def _clone_block(pk, pv, src, dst, nvalid):
                    bs = pk.shape[2]
                    valid = (jnp.arange(bs) < nvalid)[None, :, None, None]
                    kb = jnp.where(valid, jax.lax.dynamic_slice_in_dim(
                        pk, src, 1, axis=1)[:, 0],
                        jnp.zeros((), pk.dtype))
                    vb = jnp.where(valid, jax.lax.dynamic_slice_in_dim(
                        pv, src, 1, axis=1)[:, 0],
                        jnp.zeros((), pv.dtype))
                    pk = jax.lax.dynamic_update_slice(
                        pk, kb[:, None], (0, dst, 0, 0, 0))
                    pv = jax.lax.dynamic_update_slice(
                        pv, vb[:, None], (0, dst, 0, 0, 0))
                    return pk, pv

                if self.kv_dtype:
                    def copyb(pk, pv, sk, sv, src, dst, nvalid):
                        counters.inc("serving.retraces")
                        pk, pv = _clone_block(pk, pv, src, dst, nvalid)
                        bs = sk.shape[2]
                        sval = (jnp.arange(bs) < nvalid)[None, :]
                        skb = jnp.where(sval, jax.lax.dynamic_slice_in_dim(
                            sk, src, 1, axis=1)[:, 0], 0.0)
                        svb = jnp.where(sval, jax.lax.dynamic_slice_in_dim(
                            sv, src, 1, axis=1)[:, 0], 0.0)
                        sk = jax.lax.dynamic_update_slice(
                            sk, skb[:, None], (0, dst, 0))
                        sv = jax.lax.dynamic_update_slice(
                            sv, svb[:, None], (0, dst, 0))
                        return pk, pv, sk, sv
                    return jax.jit(copyb, donate_argnums=(0, 1, 2, 3))

                def copyb(pk, pv, src, dst, nvalid):
                    counters.inc("serving.retraces")
                    return _clone_block(pk, pv, src, dst, nvalid)
                return jax.jit(copyb, donate_argnums=(0, 1))
            self._pcopy_jit = self.arena.program(
                _model_programs(self.model),
                self._prog_key("copy_block"), build)
        return self._pcopy_jit

    def _pmigrate(self):
        """Block-granular KV migration: gather up to ``max_blocks``
        source-pool blocks and scatter them into destination-pool blocks
        in ONE fixed-shape dispatch.  The id vectors ride as int32
        OPERANDS padded to ``max_blocks`` (``n`` masks the live lanes),
        so the program never retraces on migration size; padded lanes
        gather the source trash block and scatter zeros back into the
        destination trash block.  Only the DESTINATION pools are donated
        — the source engine keeps serving from its arena until the fleet
        releases the migrated request (a severed migration loses
        nothing)."""
        if self._pmigrate_jit is None:
            def build():
                def _gather(spk, spv, src_ids, m5):
                    kb = jnp.take(spk, src_ids, axis=1)
                    vb = jnp.take(spv, src_ids, axis=1)
                    kb = jnp.where(m5, kb, jnp.zeros((), kb.dtype))
                    vb = jnp.where(m5, vb, jnp.zeros((), vb.dtype))
                    return kb, vb

                if self.kv_dtype:
                    def migrate(pk, pv, sk, sv, spk, spv, ssk, ssv,
                                src_ids, dst_ids, n):
                        counters.inc("serving.retraces")
                        m = jnp.arange(src_ids.shape[0]) < n
                        kb, vb = _gather(spk, spv, src_ids,
                                         m[None, :, None, None, None])
                        ids = jnp.where(m, dst_ids, 0)
                        pk = pk.at[:, ids].set(kb)
                        pv = pv.at[:, ids].set(vb)
                        m3 = m[None, :, None]
                        skb = jnp.where(
                            m3, jnp.take(ssk, src_ids, axis=1), 0.0)
                        svb = jnp.where(
                            m3, jnp.take(ssv, src_ids, axis=1), 0.0)
                        sk = sk.at[:, ids].set(skb)
                        sv = sv.at[:, ids].set(svb)
                        return pk, pv, sk, sv
                    return jax.jit(migrate, donate_argnums=(0, 1, 2, 3))

                def migrate(pk, pv, spk, spv, src_ids, dst_ids, n):
                    counters.inc("serving.retraces")
                    m = jnp.arange(src_ids.shape[0]) < n
                    kb, vb = _gather(spk, spv, src_ids,
                                     m[None, :, None, None, None])
                    ids = jnp.where(m, dst_ids, 0)
                    pk = pk.at[:, ids].set(kb)
                    pv = pv.at[:, ids].set(vb)
                    return pk, pv
                return jax.jit(migrate, donate_argnums=(0, 1))
            self._pmigrate_jit = self.arena.program(
                _model_programs(self.model),
                self._prog_key("migrate_blocks"), build)
        return self._pmigrate_jit

    def _pspill(self):
        """Host-tier spill gather: slice ONE block's K/V tiles (+ scale
        rows under quantized arenas) out of the arena in one fixed-shape
        dispatch.  Nothing is donated — the arena keeps serving; the
        caller materializes the result into pinned host buffers and only
        then releases the device block."""
        if self._pspill_jit is None:
            def build():
                if self.kv_dtype:
                    def spill(pk, pv, sk, sv, b):
                        counters.inc("serving.retraces")  # trace-time only
                        kb = jax.lax.dynamic_slice_in_dim(
                            pk, b, 1, axis=1)[:, 0]
                        vb = jax.lax.dynamic_slice_in_dim(
                            pv, b, 1, axis=1)[:, 0]
                        skb = jax.lax.dynamic_slice_in_dim(
                            sk, b, 1, axis=1)[:, 0]
                        svb = jax.lax.dynamic_slice_in_dim(
                            sv, b, 1, axis=1)[:, 0]
                        return kb, vb, skb, svb
                else:
                    def spill(pk, pv, b):
                        counters.inc("serving.retraces")  # trace-time only
                        kb = jax.lax.dynamic_slice_in_dim(
                            pk, b, 1, axis=1)[:, 0]
                        vb = jax.lax.dynamic_slice_in_dim(
                            pv, b, 1, axis=1)[:, 0]
                        return kb, vb
                return jax.jit(spill)
            self._pspill_jit = self.arena.program(
                _model_programs(self.model),
                self._prog_key("spill_block"), build)
        return self._pspill_jit

    def _prestore(self):
        """Host-tier restore scatter: write ONE block's host-side K/V
        tiles (+ scale rows) into a freshly allocated arena block, one
        fixed-shape donated dispatch — the exact inverse of
        :meth:`_pspill`, same shape family as the COW clone."""
        if self._prestore_jit is None:
            def build():
                if self.kv_dtype:
                    def restore(pk, pv, sk, sv, kb, vb, skb, svb, b):
                        counters.inc("serving.retraces")  # trace-time only
                        pk = jax.lax.dynamic_update_slice(
                            pk, kb[:, None], (0, b, 0, 0, 0))
                        pv = jax.lax.dynamic_update_slice(
                            pv, vb[:, None], (0, b, 0, 0, 0))
                        sk = jax.lax.dynamic_update_slice(
                            sk, skb[:, None], (0, b, 0))
                        sv = jax.lax.dynamic_update_slice(
                            sv, svb[:, None], (0, b, 0))
                        return pk, pv, sk, sv
                    return jax.jit(restore, donate_argnums=(0, 1, 2, 3))

                def restore(pk, pv, kb, vb, b):
                    counters.inc("serving.retraces")  # trace-time only
                    pk = jax.lax.dynamic_update_slice(
                        pk, kb[:, None], (0, b, 0, 0, 0))
                    pv = jax.lax.dynamic_update_slice(
                        pv, vb[:, None], (0, b, 0, 0, 0))
                    return pk, pv
                return jax.jit(restore, donate_argnums=(0, 1))
            self._prestore_jit = self.arena.program(
                _model_programs(self.model),
                self._prog_key("restore_block"), build)
        return self._prestore_jit

    # -- host-RAM KV tier ----------------------------------------------------
    # All helpers below run with ``_cond`` held by the caller: spill and
    # restore are part of atomic reservation / export transitions, same
    # contract as the COW and migration adopts.  Each is a bounded
    # number of one-block dispatches, never a per-token loop.
    def _spill_block(self, block):
        """Device→host copy of ONE block into reuse-pool buffers
        (returned).  ``np.asarray`` materializes the gather before the
        copy, so the device block is reusable the moment this
        returns."""
        sp = self._pspill()
        _dt = _devicetime.note(f"serving.kv.{self._prog_key('spill_block')}")
        if self.kv_dtype:
            out = sp(self._pk, self._pv, self._sk, self._sv,
                     np.int32(block))
        else:
            out = sp(self._pk, self._pv, np.int32(block))
        _devicetime.observe(_dt, out)
        bufs = self._host_tier.acquire(self._host_spec)
        for dst, src in zip(bufs, out):
            np.copyto(dst, np.asarray(src))
        return bufs

    def _restore_block(self, block, bufs):
        """Host→device scatter of one tier entry into ``block``.  The
        numpy buffers ride the dispatch as operands and may be aliased
        by the backend (CPU jax aliases host arrays zero-copy): callers
        must sync (``jax.block_until_ready``) before recycling them."""
        rs = self._prestore()
        _dt = _devicetime.note(
            f"serving.kv.{self._prog_key('restore_block')}")
        if self.kv_dtype:
            (self._pk, self._pv, self._sk, self._sv) = rs(
                self._pk, self._pv, self._sk, self._sv, *bufs,
                np.int32(block))
        else:
            self._pk, self._pv = rs(self._pk, self._pv, *bufs,
                                    np.int32(block))
        _devicetime.observe(_dt, (self._pk, self._pv))

    def _drop_host_key(self, key):
        """Reconcile bookkeeping for a key the tier LRU-discarded: a
        prefix node drops its (all-host) subtree; a spilled-request
        shard marks the request's spill set lost, so export replays it
        by re-prefill instead of restoring."""
        if isinstance(key, tuple) and key and key[0] == "req":
            ent = self._req_host.get(key[1])
            if ent is not None:
                ent["idx"].discard(key[2])
                ent["lost"] = True
            counters.inc("serving.kv.tier.spill_drops")
        else:
            self.prefix.drop_host(key)

    def _spill_cold(self, want):
        """Spill up to ``want`` cold prefix-tree blocks to the host
        tier, coldest first, freeing their device blocks.  Runs BEFORE
        LRU eviction on shortfall, so oversubscription demotes prefixes
        instead of destroying them.  Returns blocks freed."""
        freed = 0
        while freed < want:
            victims = self.prefix.spill_victims(want - freed)
            if not victims:
                break
            for v in victims:
                bufs = self._spill_block(v.block)
                self.prefix.mark_spilled(v)
                self.kv_tier_spilled += 1
                for k in self._host_tier.put(v, bufs):
                    self._drop_host_key(k)
                freed += 1
        return freed

    def _restore_prefix(self, tokens, limit, rid):
        """Page the host-resident chain extending this prompt's device
        match back into fresh device blocks, so the subsequent
        ``PrefixCache.match`` adopts them like any cached prefix.
        Under the ``kv_spill_drop`` fault the chain's host copies are
        dropped instead — the prompt becomes a plain miss and the
        fresh prefill IS the deterministic replay.  Returns blocks
        restored."""
        from ..resilience import faultinject as _fi
        chain = self.prefix.host_chain(tokens, limit)
        if not chain:
            return 0
        if _fi.take("kv_spill_drop", rid):
            dropped = self.prefix.drop_host(chain[0])
            flight.record("serving.kv.tier.spill_drop", rid=rid,
                          nodes=dropped, where="prefix_restore")
            return 0
        restored = []
        for node in chain:
            bufs = self._host_tier.get(node)
            if bufs is None:
                # overflow discarded the entry between walk and get:
                # the rest of the chain is a miss now
                self.prefix.drop_host(node)
                break
            if self.pool.free_blocks == 0:
                self.prefix.evict(1)
                if self.pool.free_blocks == 0:
                    break
            block = self.pool.alloc()
            self._restore_block(block, bufs)
            self.prefix.mark_restored(node, block)
            self.kv_tier_restored += 1
            restored.append(node)
        if restored:
            # the restore scatters may alias the tier buffers on CPU
            # backends — one sync for the whole chain, then recycle
            jax.block_until_ready(self._pk)
            for node in restored:
                self._host_tier.pop(node)
        return len(restored)

    def _maybe_spill_idle(self):
        """Held (disaggregation hand-off) requests that sit idle past
        ``spill_idle_steps`` scheduler steps spill their private KV to
        the host tier; ``export_request`` pages it back before
        snapshotting.  One sweep per :meth:`step`."""
        if self._host_tier is None or self.spill_idle_steps <= 0:
            return
        with self._cond:
            live = {r.rid: (s, r) for s, r in enumerate(self._slots)
                    if r is not None and r.state == "held"
                    and r.rid not in self._req_host}
            self._held_idle = {rid: self._held_idle.get(rid, 0) + 1
                               for rid in live}
            for rid, steps in list(self._held_idle.items()):
                if steps >= self.spill_idle_steps:
                    slot, req = live[rid]
                    self._spill_request(slot, req)
                    del self._held_idle[rid]

    def _spill_request(self, slot, req):
        """Move a held request's PRIVATE data blocks (refcount 1, below
        the write frontier) to the host tier and trash their table
        entries; shared prefix blocks stay device-side.  The freed
        blocks fund new admissions while the request waits for its
        decode-replica migration.  Caller holds ``_cond``."""
        table = self._slot_blocks[slot]
        pos = int(self._pos[slot])
        n_data = blocks_for_tokens(max(pos, 1), self.pool.block_size)
        ent = {"idx": set(), "lost": False}
        for i in range(n_data):
            b = table[i]
            if b == TRASH_BLOCK or self.pool.ref(b) != 1:
                continue
            bufs = self._spill_block(b)
            for k in self._host_tier.put(("req", req.rid, i), bufs):
                self._drop_host_key(k)
            self.pool.release(b)
            table[i] = TRASH_BLOCK
            self._write_slot((slot, i), bt=0)
            ent["idx"].add(i)
            counters.inc("serving.kv.tier.spilled_blocks")
            self.kv_tier_spilled += 1
        if ent["idx"]:
            self._req_host[req.rid] = ent
            flight.record("serving.kv.tier.req_spilled", rid=req.rid,
                          blocks=len(ent["idx"]))

    def _restore_request(self, req):
        """Page a spilled held request's KV back into fresh device
        blocks so :meth:`export_request` can snapshot a fully
        device-resident table.  Raises :class:`HostTierLost` when the
        host copy is gone (tier overflow or the ``kv_spill_drop``
        fault) — the fleet requeues the request for deterministic
        replay — and ``EngineBackpressure`` when the pool cannot host
        the restore yet (partial progress is kept; the deferred export
        resumes where it stopped).  Caller holds ``_cond``."""
        from ..resilience import faultinject as _fi
        ent = self._req_host.get(req.rid)
        if ent is None:
            return
        slot = req.slot
        table = self._slot_blocks[slot]
        if ent["lost"] or _fi.take("kv_spill_drop", req.rid):
            for i in list(ent["idx"]):
                self._host_tier.pop(("req", req.rid, i))
                counters.inc("serving.kv.tier.spill_drops")
            del self._req_host[req.rid]
            flight.record("serving.kv.tier.spill_drop", rid=req.rid,
                          nodes=len(table), where="request_restore")
            raise HostTierLost(
                f"request {req.rid}: spilled KV lost before restore")
        restored, err = [], None
        for i in sorted(ent["idx"]):
            bufs = self._host_tier.get(("req", req.rid, i))
            if bufs is None:
                ent["lost"] = True
                break
            if self.pool.free_blocks == 0 and self.prefix is not None:
                self.prefix.evict(1)
            if self.pool.free_blocks == 0:
                err = EngineBackpressure(
                    "host-tier restore needs free blocks",
                    queue_depth=len(self._queue),
                    retry_after_hint=self._retry_hint_locked())
                break
            b = self.pool.alloc()
            self._restore_block(b, bufs)
            table[i] = b
            self._write_slot((slot, i), bt=b)
            restored.append(i)
        if restored:
            jax.block_until_ready(self._pk)
            for i in restored:
                ent["idx"].discard(i)
                self._host_tier.pop(("req", req.rid, i))
            counters.inc("serving.kv.tier.restored_blocks", len(restored))
            self.kv_tier_restored += len(restored)
        if ent["lost"]:
            for i in list(ent["idx"]):
                self._host_tier.pop(("req", req.rid, i))
                counters.inc("serving.kv.tier.spill_drops")
            del self._req_host[req.rid]
            raise HostTierLost(
                f"request {req.rid}: spilled KV lost mid-restore")
        if err is not None:
            raise err
        del self._req_host[req.rid]
        flight.record("serving.kv.tier.req_restored", rid=req.rid,
                      blocks=len(restored))

    # -- request intake ------------------------------------------------------
    def add_request(self, prompt, max_new_tokens=32, **kw):
        tenant = kw.get("adapter")
        if tenant is not None:
            # refuse unregistered tenants HERE, synchronously — admission
            # runs on the scheduler thread, where a KeyError would
            # poison the whole step, not just this request
            if self.adapters is None:
                raise ValueError("adapter given but the engine was "
                                 "built with adapter_slots=0")
            with self._cond:
                if tenant not in self.adapters._registry:
                    raise KeyError(
                        f"adapter {tenant!r} is not registered on this "
                        "engine (register_adapter first)")
        ids = np.asarray(
            prompt._data if hasattr(prompt, "_data") else prompt,
            dtype=np.int32).reshape(-1)
        need = self._blocks_needed(int(ids.shape[0]), int(max_new_tokens))
        if need > self.pool.capacity:
            raise ValueError(
                f"request needs {need} KV blocks but the pool only has "
                f"{self.pool.capacity} (n_blocks={self.n_blocks}, "
                f"block_size={self.pool.block_size})")
        return super().add_request(ids, max_new_tokens=max_new_tokens, **kw)

    # -- admission: all-or-nothing block reservation -------------------------
    def _blocks_needed(self, T, max_new):
        """K/V blocks a request of ``T`` prompt tokens can ever touch: the
        last token sampled is never written back."""
        return blocks_for_tokens(max(1, T + max_new - 1),
                                 self.pool.block_size)

    def _reserve(self, req, events):
        """Match the prefix cache, then reserve every block the request
        can ever touch (``ceil((T + max_new - 1)/bs)`` minus shared
        prefix blocks).  Returns False — with NOTHING allocated and no
        table mutated — when the pool (after LRU eviction) cannot cover
        it, or when the ``kv_pool_exhausted`` fault is scheduled for
        this request id."""
        from ..resilience import faultinject as _fi
        T = int(req.prompt.shape[0])
        total = self._blocks_needed(T, req.max_new_tokens)
        tr = req.trace
        t0_tr = time.perf_counter_ns() if tr is not None else 0
        with self._cond:
            injected = _fi.take("kv_pool_exhausted", req.rid)
            aslot = 0
            if self.adapters is not None and req.adapter is not None:
                # pin the tenant's LoRA slot FIRST (a cold tenant pages
                # in here, one bounded donated dispatch — part of the
                # atomic reservation like the COW adopt below); a full
                # arena or an injected adapter_load_drop defers the
                # request exactly like KV exhaustion, nothing allocated
                from .adapters import AdapterArenaExhausted
                try:
                    aslot = self.adapters.acquire(req.adapter,
                                                  rid=req.rid)
                except AdapterArenaExhausted as e:
                    flight.record("serving.adapter.exhausted",
                                  rid=req.rid, tenant=str(req.adapter),
                                  needed=e.needed, free=e.free)
                    return False
            shared, cached, pnode, p = [], 0, None, 0
            if self.prefix is not None and not injected:
                pkey = self._prefix_key(req.prompt.tolist(), req.adapter)
                if self._host_tier is not None:
                    # page host-resident prefix blocks back in first so
                    # the match below adopts them like any cached prefix
                    self._restore_prefix(pkey, T - 1, req.rid)
                shared, cached, pnode, p = self.prefix.match(pkey, T - 1)
            fresh_needed = total - len(shared)
            shortfall = fresh_needed - self.pool.free_blocks
            if shortfall > 0 and self.prefix is not None:
                if self._host_tier is not None:
                    # spill-before-evict: demote cold prefixes to host
                    # RAM instead of destroying them
                    self._spill_cold(shortfall)
                    shortfall = fresh_needed - self.pool.free_blocks
                if shortfall > 0:
                    self.kv_blocks_evicted += self.prefix.evict(shortfall)
                    shortfall = fresh_needed - self.pool.free_blocks
            if injected or shortfall > 0:
                for b in shared:
                    self.pool.release(b)
                if pnode is not None:
                    self.pool.release(pnode.block)
                if aslot:
                    # unwind the adapter pin; the tenant stays resident
                    # at refcount 0 so the retry re-acquires it warm
                    self.adapters.release(req.adapter)
                self.kv_pool_exhausted_events += 1
                counters.inc("serving.kv.pool_exhausted")
                flight.record("serving.kv.pool_exhausted", rid=req.rid,
                              needed=fresh_needed,
                              free=self.pool.free_blocks,
                              injected=bool(injected))
                return False
            fresh = self.pool.alloc_n(fresh_needed)
            table = shared + fresh
            slot = self._free.pop()
            if tr is not None:
                tr.add_span("kv.reserve", t0_tr, time.perf_counter_ns(),
                            blocks=len(table), shared=len(shared),
                            cached=cached)
            if pnode is not None:
                # copy-on-write: clone the shared partial block into the
                # request's first private tail block before extending it
                t0_cow = time.perf_counter_ns() if tr is not None else 0
                cp = self._pcopy()
                scalars = (np.int32(pnode.block),
                           np.int32(table[len(shared)]), np.int32(p))
                if self.kv_dtype:
                    cargs = (self._pk, self._pv, self._sk, self._sv,
                             *scalars)
                    dn = (0, 1, 2, 3)
                else:
                    cargs = (self._pk, self._pv, *scalars)
                    dn = (0, 1)
                cow_name = f"serving.kv.{self._prog_key('copy_block')}"
                self._maybe_capture(cow_name, cp, *cargs)
                self._maybe_audit(cow_name, cp, *cargs,
                                  donate_argnums=dn)
                # the reservation (pool alloc + table + COW adopt) must be
                # atomic w.r.t. concurrent cancel/router stats, so this one
                # bounded block-copy dispatch stays under the lock
                _dt = _devicetime.note(cow_name)
                # ptlint: disable=PT005 reason="COW adopt is part of the atomic reservation; a bounded one-block copy, not a per-token dispatch"
                out = cp(*cargs)
                _devicetime.observe(_dt, out)
                if self.kv_dtype:
                    self._pk, self._pv, self._sk, self._sv = out
                else:
                    self._pk, self._pv = out
                if tr is not None:
                    tr.add_span("cow.adopt", t0_cow,
                                time.perf_counter_ns(), tokens=p)
                self.pool.release(pnode.block)   # drop the match retain
                cached += p
                self.kv_cow_copies += 1
                counters.inc("serving.kv.cow_copies")
            if cached > 0:
                self.kv_prefix_hits += 1
                self.kv_prefix_hit_tokens += cached
                counters.inc("serving.kv.prefix_hits")
                counters.inc("serving.kv.prefix_hit_tokens", cached)
            else:
                self.kv_prefix_misses += 1
                counters.inc("serving.kv.prefix_misses")
            self._slot_blocks[slot] = table
            self._write_slot(slot, bt=self._table_row(table, slot),
                             aid=aslot, running=False)
            req.state = "prefilling"
            req.slot = slot
            self._slots[slot] = req
            self._prefill_state[slot] = {"req": req, "done": cached}
        flight.record("serving.kv.admit", rid=req.rid, blocks=len(table),
                      shared=len(shared), cached_tokens=cached)
        events.append({"type": "admitted", "request": req})
        return True

    def _table_row(self, table, slot):
        """Slot ``slot``'s table: its full-pool blocks, then (a model with
        window layers) the slot's own ring in the window pool."""
        row = np.zeros(self.max_blocks + self.window_entries, np.int32)
        row[:len(table)] = table
        if self.window:
            row[self.max_blocks:] = self._ring[slot]
        return row

    def _admit(self, events):
        now = time.monotonic()
        while self._free:
            with self._cond:
                if not self._queue:
                    return
                req = self._queue.popleft()
                self._cond.notify()
            if req._cancel:
                self._finish(req, "cancelled", events)
                continue
            if req.deadline is not None and now > req.deadline:
                counters.inc("serving.deadline_expired")
                self._finish(req, "deadline", events)
                continue
            if not self._reserve(req, events):
                # pool exhausted (real or injected): park the request back
                # at the queue head and stop admitting this step — blocks
                # free as running requests finish, and callers see the
                # backlog as EngineBackpressure with a drain-rate hint
                with self._cond:
                    self._queue.appendleft(req)
                return
            self._observe("serving.queue_wait_ns",
                          time.perf_counter_ns() - req.arrival_ns,
                          sum_counter=True)
            if req.trace is not None:
                req.trace.span_from("enqueue", "queue")

    # -- chunked prefill, interleaved with decode ----------------------------
    def _run_chunk(self, slot, st, events):
        req = st["req"]
        T = int(req.prompt.shape[0])
        start = st["done"]
        remaining = T - start
        C = bucket_length(min(remaining, self.prefill_chunk),
                          self.min_bucket, self.prefill_chunk)
        take_n = min(remaining, C)
        last = start + take_n == T
        with span("serving.prefill.operands"):
            ids = np.zeros((1, C), np.int32)
            ids[0, :take_n] = req.prompt[start:start + take_n]
            op = self.arena.operand
            if "key" not in st:
                # what is constant for the request is made with its first
                # chunk.  Every chunk is fed the request's ORIGINAL seed
                # key; only the final chunk's sample/key are consumed, so
                # the key-split chain is exactly generate's
                # one-split-after-prefill.  The slot's table was written
                # whole at admission and is not written again before the
                # last chunk; the adapter row ([1]-shaped to match the
                # chunk's batch) likewise
                st["key"] = np.asarray(
                    jax.random.key_data(jax.random.key(req.seed)))
                st["bt"] = op(self._bt[slot].copy())
                st["aid"] = (op(np.asarray([self._aid[slot]], np.int32))
                             if self.adapters is not None else None)
            self._observe("serving.prefill_occupancy", take_n / C)
            tr = req.trace
            t0_tr = time.perf_counter_ns() if tr is not None else 0
            pf = self._pchunk_for(C)
            head = (self._w, op(ids), np.int32(start), np.int32(take_n),
                    st["bt"])
            tail = (st["key"], np.bool_(req.do_sample),
                    np.float32(req.temperature), np.int32(req.top_k),
                    np.float32(req.top_p))
            if self.adapters is not None:
                # slab pytree + this request's arena row as trailing
                # operands
                tail = tail + (self.adapters.slabs(), st["aid"])
            if self._state_names:
                pargs = (*head, self._pk, self._pv, self._st,
                         np.int32(slot), *tail)
                dn = (5, 6, 7)
            elif self.kv_dtype:
                pargs = (*head, self._pk, self._pv, self._sk, self._sv,
                         *tail)
                dn = (5, 6, 7, 8)
            else:
                pargs = (*head, self._pk, self._pv, *tail)
                dn = (5, 6)
            pname = f"serving.{self._prog_key('prefill_paged')}[c{C}]"
            self._maybe_capture(pname, pf, *pargs)
            self._maybe_audit(pname, pf, *pargs, donate_argnums=dn)
        with self._launch_span("serving.prefill.dispatch"):
            _dt = _devicetime.note(pname)
            if self._state_names:
                self._pk, self._pv, self._st, tok, new_key = pf(*pargs)
            elif self.kv_dtype:
                (self._pk, self._pv, self._sk, self._sv, tok,
                 new_key) = pf(*pargs)
            else:
                self._pk, self._pv, tok, new_key = pf(*pargs)
            _devicetime.observe(_dt, tok)
        if tr is not None:
            tr.add_span("prefill.chunk", t0_tr, time.perf_counter_ns(),
                        chunk=C, start=start, take=take_n)
        counters.inc("serving.kv.prefill_chunks")
        if self.window:
            # window-ring entries this chunk took over from a block the
            # row had passed: blocks it began at or past the ring's end
            bs = self.block_size
            begun = max(-(-start // bs), self.window_entries)
            counters.inc("serving.kv.window_blocks_recycled",
                         max(0, (start + take_n - 1) // bs - begun + 1))
        if self.kv_dtype:
            counters.inc("serving.kv.quant.prefill_tokens", take_n)
        st["done"] = start + take_n
        if last:
            del self._prefill_state[slot]
            counters.inc("serving.prefill_batches")
            # only the last chunk's sample is consumed: the one read-back
            with span("serving.prefill.wait") as w:
                tok, new_key = int(tok), np.asarray(new_key)
            self._drained(w)
            with span("serving.prefill.emit"):
                with self._cond:
                    self._write_slot(
                        slot, tok=tok, pos=T, keys=new_key,
                        temp=req.temperature, topk=req.top_k,
                        topp=req.top_p, dosample=req.do_sample,
                        running=not req.hold)
                if req.hold:
                    # disaggregated hand-off point: the row parks instead
                    # of entering decode — _running stays False so the
                    # decode launch tables it to the trash block — until
                    # the fleet migrates its block table to a decode
                    # replica.  The first token was already sampled by
                    # the final chunk, so it is emitted here (TTFT is a
                    # prefill-side metric); _emit may finish the request
                    # (EOS / max_new == 1), in which case there is
                    # nothing left to migrate.
                    req.state = "held"
                    self._emit(req, tok, events)
                    if req.state == "held":
                        events.append({"type": "prefilled", "request": req})
                else:
                    req.state = "running"
                    self._emit(req, tok, events)

    def _prefill_chunks(self, events):
        """One chunk per prefilling slot per step (round-robin in slot
        order): a long prompt advances ``prefill_chunk`` tokens per
        scheduler iteration while every running request still gets its
        decode token — chunked prefill can never starve ITL."""
        from ..resilience import faultinject as _fi
        for slot in sorted(self._prefill_state):
            st = self._prefill_state.get(slot)
            if st is None or st["req"].is_finished:
                continue
            req = st["req"]
            try:
                _fi.maybe_fault("serving_prefill", req.rid)
                self._run_chunk(slot, st, events)
            except Exception as e:
                # a poisoned request (bad prompt, injected fault, prefill
                # blow-up) must not kill the engine loop: contain it to
                # finish_reason="error" and free its slot + blocks
                req.error = e
                counters.inc("serving.request_errors")
                self._finish(req, "error", events)

    # -- decode over block tables --------------------------------------------
    def _decode_step(self, events):
        flight = self._inflight
        if flight is not None and (self._must_upload()
                                   or self._leaves_with(flight)):
            # the mirrors lag the launch in flight by one: read it back
            # before one of them goes up, and before a row that gets its
            # last token from it would run once more
            self._settle(events)
        active = [(s, r) for s, r in enumerate(self._slots)
                  if r is not None and r.state == "running"]
        if not active:
            self._settle(events)
            return
        with span("serving.decode.operands"):
            # one clock pair a launch: this one, and at its read-back the
            # other (the tokens/s EMA, the request trace's decode.iter,
            # the drained stamp)
            t0 = time.perf_counter_ns()
            self._observe("serving.decode_occupancy",
                          len(active) / self.max_slots)
            dec = self._pdecode()
            # on most launches no slot changed hands since the last one:
            # every operand is then a device array that launch left, and
            # nothing is uploaded
            tail, uploaded = self._decode_operands()
            behind = self._inflight is not None
            sampled = bool((self._dosample & self._running).any())
            if self._state_names:
                # the rows' recurrent state rides next to the pools; a row
                # that is not running keeps its own bit for bit
                dargs = (self._w, self._pk, self._pv, self._st, *tail)
                dn = (1, 2, 3)
            elif self.kv_dtype:
                dargs = (self._w, self._pk, self._pv, self._sk, self._sv,
                         *tail)
                dn = (1, 2, 3, 4)
            else:
                dargs = (self._w, self._pk, self._pv, *tail)
                dn = (1, 2)
            dname = f"serving.{self._prog_key('decode_paged')}"
            self._maybe_capture(dname, dec, *dargs)
            self._maybe_audit(dname, dec, *dargs, donate_argnums=dn)
            counters.inc("serving.decode_steps")
            counters.inc("serving.decode.sampled_steps", int(sampled))
            counters.inc("serving.decode.upload_steps", int(uploaded))
            counters.inc("serving.decode.overlapped_steps", int(behind))
        with self._launch_span("serving.decode.dispatch"):
            _dt = _devicetime.note(dname)
            if self._state_names:
                (nxt, self._pk, self._pv, self._st, pos,
                 keys) = dec(*dargs)
            elif self.kv_dtype:
                (nxt, self._pk, self._pv, self._sk, self._sv, pos,
                 keys) = dec(*dargs)
            else:
                nxt, self._pk, self._pv, pos, keys = dec(*dargs)
            _devicetime.observe(_dt, nxt)
            # the program's own outputs are the next launch's operands
            launch = _Launch(active, nxt, {"tok": nxt, "pos": pos,
                                           "keys": keys},
                             t0, self._launches)
        # this launch is queued: now the one before it is read back
        self._settle(events)
        self._inflight = launch

    def _must_upload(self):
        """Whether the next decode launch uploads an operand."""
        with self._cond:
            return not self._stale.isdisjoint(self._operand_names)

    def _leaves_with(self, flight):
        """Whether a row of the launch in flight gets its request's last
        token from it (a length finish, which the host knows a launch
        ahead; an end-of-sequence token it learns at the read-back)."""
        return any(self._slots[s] is r and r.state == "running"
                   and len(r.tokens) + 1 >= r.max_new_tokens
                   for s, r in flight.rows)

    def _settle(self, events):
        """Read the decode launch in flight back and emit its tokens into
        ``events``; nothing to do when none is in flight.  The one place a
        launch's tokens reach the host.  A row's mirrors move on and it
        emits only where the request the launch ran it for still holds
        the slot and runs: a row whose request finished at the launch
        before (an end-of-sequence token) ran once more, past its live
        positions, and that token is dropped.  The read-back stamps the
        drain only when no launch of the engine is queued behind it."""
        flight, self._inflight = self._inflight, None
        if flight is None:
            return
        with span("serving.decode.wait") as w:    # the one read-back
            nxt = np.asarray(flight.nxt)
        t1 = time.perf_counter_ns()
        if flight.seq == self._launches:
            self._drained(w, t1)
        with span("serving.decode.emit"):
            kept = [(s, r) for s, r in flight.rows
                    if self._slots[s] is r and r.state == "running"]
            with self._cond:
                # what the launch carried forward is the mirrors' now
                self._dev.update(flight.carried)
                if self._keys_host is not None:
                    if "keys" in self._stale:
                        # a row written since the dispatch keeps its key
                        moved = np.asarray(flight.carried["keys"])
                        for s, _ in kept:
                            self._keys_host[s] = moved[s]
                    else:
                        self._keys_host = None
                if self.window:
                    # a launch that wrote a block's first position at or
                    # past the ring's end took over that entry
                    bs, n = self.block_size, self.window_entries
                    counters.inc("serving.kv.window_blocks_recycled", sum(
                        self._pos[s] % bs == 0 and self._pos[s] // bs >= n
                        for s, _ in kept))
                for s, _ in kept:
                    self._tok[s] = nxt[s]
                    self._pos[s] += 1
            # the launch's time: from its operands, or from the read-back
            # before it if it was queued behind that launch
            t0 = max(flight.t0, self._read_ns)
            self._read_ns = t1
            if kept:
                if rtrace.enabled():
                    for _s, r in kept:
                        if r.trace is not None:
                            r.trace.add_span("decode.iter", t0, t1,
                                             batch=len(kept))
                self._note_decode(len(kept), (t1 - t0) * 1e-9)
            counters.inc("serving.decode_tokens", len(kept))
            if self.kv_dtype:
                counters.inc("serving.kv.quant.decode_tokens", len(kept))
            for s, req in kept:
                self._emit(req, nxt[s], events)

    # -- KV migration (disaggregated prefill/decode fleet) -------------------
    def export_request(self, req):
        """Snapshot a held request's migration payload: block table,
        decode-state row and committed tokens — NO device copies and no
        mutation, so the source stays fully intact until
        :meth:`finish_migrated` and a migration severed in flight loses
        nothing.  KV is valid for positions ``[0, pos)``; the last
        committed token (``tok``) was sampled but never written back —
        exactly the prefix-tree donation contract."""
        self._refuse_recurrent("export_request")
        with self._cond:
            slot = req.slot
            if slot is None or req.state != "held":
                raise RuntimeError(
                    f"request {req.rid} is not held for migration "
                    f"(state={req.state!r})")
            if self._host_tier is not None:
                # an idle-spilled request pages its KV back before the
                # snapshot (raises HostTierLost / EngineBackpressure —
                # the fleet replays or defers, nothing is torn here)
                self._restore_request(req)
            return {
                "prompt": req.prompt,
                "tokens": list(req.tokens),
                "max_new_tokens": req.max_new_tokens,
                "do_sample": req.do_sample,
                "temperature": req.temperature,
                "top_k": req.top_k,
                "top_p": req.top_p,
                "eos_token_id": req.eos_token_id,
                "seed": req.seed,
                "deadline": req.deadline,
                "arrival_ns": req.arrival_ns,
                "last_emit_ns": req.last_emit_ns,
                "tok": int(self._tok[slot]),
                "pos": int(self._pos[slot]),
                "key": np.array(self._keys[slot]),
                "table": list(self._slot_blocks[slot]),
                "block_size": self.pool.block_size,
                "kv_dtype": self.kv_dtype,
                "adapter": req.adapter,
            }

    def adopt_migration(self, mig, src, trace_ctx=None):
        """Install a migrated request on THIS engine (the decode side of
        the hand-off).  The prefix is re-resolved against the
        destination's OWN radix tree: full data blocks already cached
        here are adopted by refcount transfer (``PrefixCache.match_full``
        retains them on this pool — a shared prefix never moves twice),
        and only the unshared tail of the source block table is
        device-copied, in one bounded :meth:`_pmigrate` dispatch.  Raises
        ``EngineBackpressure`` / ``BlockPoolExhausted`` with NOTHING
        allocated when this engine cannot host the request (the fleet
        then replays it by deterministic re-prefill).

        Returns ``(request, info)``; the installed request is already
        ``"running"`` with the migrated tokens replayed into its stream
        state, so its next emitted token continues the source's ITL
        chain."""
        self._refuse_recurrent("adopt_migration")
        if (self.pool.block_size != mig["block_size"]
                or self.kv_dtype != mig["kv_dtype"]):
            raise ValueError(
                "KV migration between incompatible paged engines "
                f"(block_size {self.pool.block_size} vs "
                f"{mig['block_size']}, kv_dtype {self.kv_dtype!r} vs "
                f"{mig['kv_dtype']!r})")
        bs = self.pool.block_size
        pos = int(mig["pos"])
        total = len(mig["table"])
        if total > self.max_blocks:
            raise ValueError(
                f"migrated table ({total} blocks) exceeds this engine's "
                f"max_blocks ({self.max_blocks})")
        n_data = blocks_for_tokens(max(pos, 1), bs)
        seq = np.concatenate(
            [mig["prompt"], np.asarray(mig["tokens"], np.int32)])[:pos]
        t0_tr = time.perf_counter_ns() if trace_ctx is not None else 0
        with self._cond:
            if self._closed:
                raise EngineClosed("engine is drained; cannot adopt")
            if not self._free:
                raise EngineBackpressure(
                    "no free decode slot for migration",
                    queue_depth=len(self._queue),
                    retry_after_hint=self._retry_hint_locked())
            mig_ad = mig.get("adapter")
            aslot = 0
            if mig_ad is not None:
                # the destination re-acquires by tenant name against its
                # OWN arena/registry — adapter factors never ride the
                # migration payload.  A full arena (or an engine without
                # adapters) refuses with nothing allocated; the fleet
                # replays by deterministic re-prefill.
                from .adapters import AdapterArenaExhausted
                if self.adapters is None:
                    raise ValueError(
                        f"migrated request carries adapter {mig_ad!r} "
                        "but this engine was built with adapter_slots=0")
                try:
                    aslot = self.adapters.acquire(mig_ad)
                except (AdapterArenaExhausted, KeyError) as e:
                    raise EngineBackpressure(
                        f"adapter arena cannot host migrated tenant "
                        f"{mig_ad!r}: {e}",
                        queue_depth=len(self._queue),
                        retry_after_hint=self._retry_hint_locked()) \
                        from e
            shared, cached = [], 0
            if self.prefix is not None:
                pkey = self._prefix_key(seq.tolist(), mig_ad)
                if self._host_tier is not None:
                    # a host-resident prefix counts as "held here" for
                    # the router's cost model — page it in so the
                    # match below shares it instead of copying
                    self._restore_prefix(pkey, (pos // bs) * bs, -1)
                # only whole blocks strictly below the write frontier are
                # shareable: the block holding position ``pos`` will be
                # written by the next decode step and must stay private
                shared, cached = self.prefix.match_full(
                    pkey, (pos // bs) * bs)
            n_shared = len(shared)
            fresh_needed = total - n_shared
            shortfall = fresh_needed - self.pool.free_blocks
            if shortfall > 0 and self.prefix is not None:
                if self._host_tier is not None:
                    self._spill_cold(shortfall)
                    shortfall = fresh_needed - self.pool.free_blocks
                if shortfall > 0:
                    self.kv_blocks_evicted += self.prefix.evict(shortfall)
                    shortfall = fresh_needed - self.pool.free_blocks
            if shortfall > 0:
                for b in shared:
                    self.pool.release(b)
                if aslot:
                    self.adapters.release(mig_ad)
                self.kv_pool_exhausted_events += 1
                counters.inc("serving.kv.pool_exhausted")
                flight.record("serving.kv.pool_exhausted",
                              migration=True, needed=fresh_needed,
                              free=self.pool.free_blocks)
                raise BlockPoolExhausted(
                    f"migration needs {fresh_needed} blocks, "
                    f"{self.pool.free_blocks} free",
                    needed=fresh_needed, free=self.pool.free_blocks)
            fresh = self.pool.alloc_n(fresh_needed)
            table = shared + fresh
            n_copy = n_data - n_shared
            if n_copy > 0:
                src_ids = np.zeros(self.max_blocks, np.int32)
                dst_ids = np.zeros(self.max_blocks, np.int32)
                src_ids[:n_copy] = mig["table"][n_shared:n_data]
                dst_ids[:n_copy] = table[n_shared:n_data]
                mg = self._pmigrate()
                scalars = (src_ids, dst_ids, np.int32(n_copy))
                if self.kv_dtype:
                    margs = (self._pk, self._pv, self._sk, self._sv,
                             src._pk, src._pv, src._sk, src._sv,
                             *scalars)
                    dn = (0, 1, 2, 3)
                else:
                    margs = (self._pk, self._pv, src._pk, src._pv,
                             *scalars)
                    dn = (0, 1)
                mg_name = f"serving.kv.{self._prog_key('migrate_blocks')}"
                self._maybe_capture(mg_name, mg, *margs)
                self._maybe_audit(mg_name, mg, *margs, donate_argnums=dn)
                # the adopt (dest prefix retains + alloc + table install
                # + block copy) must be atomic w.r.t. this engine's
                # scheduler — same contract as the COW adopt in _reserve
                _dt = _devicetime.note(mg_name)
                # ptlint: disable=PT005 reason="migration adopt is one bounded block-table copy inside the atomic reservation, not a per-token dispatch"
                out = mg(*margs)
                _devicetime.observe(_dt, out)
                if self.kv_dtype:
                    self._pk, self._pv, self._sk, self._sv = out
                else:
                    self._pk, self._pv = out
            if cached > 0:
                self.kv_prefix_hits += 1
                self.kv_prefix_hit_tokens += cached
                counters.inc("serving.kv.prefix_hits")
                counters.inc("serving.kv.prefix_hit_tokens", cached)
            else:
                self.kv_prefix_misses += 1
                counters.inc("serving.kv.prefix_misses")
            req = Request(next(self._rid), mig["prompt"],
                          int(mig["max_new_tokens"]),
                          bool(mig["do_sample"]),
                          float(mig["temperature"]), int(mig["top_k"]),
                          float(mig["top_p"]), mig["eos_token_id"],
                          int(mig["seed"]), mig["deadline"], self)
            req.tokens = list(mig["tokens"])
            req.arrival_ns = mig["arrival_ns"]
            req.last_emit_ns = mig["last_emit_ns"]
            req.trace = trace_ctx
            req.adapter = mig_ad
            req.state = "running"
            slot = self._free.pop()
            req.slot = slot
            self._slots[slot] = req
            self._slot_blocks[slot] = table
            self._write_slot(
                slot, bt=self._table_row(table, slot), aid=aslot, running=True,
                tok=int(mig["tok"]), pos=pos, keys=np.asarray(mig["key"]),
                temp=req.temperature, topk=req.top_k, topp=req.top_p,
                dosample=req.do_sample)
            self._outstanding += max(
                0, req.max_new_tokens - len(req.tokens))
            self._adopt_extra(slot, req, mig)
            if self.prefix is not None and pos // bs > 0:
                # migrated prefixes re-enter THIS tree immediately: the
                # blocks below the write frontier are never mutated, so
                # the next same-prefix prompt or migration shares them
                # without waiting for this request to finish and donate
                n_full = pos // bs
                self.prefix.insert(
                    self._prefix_key(seq[:n_full * bs].tolist(), mig_ad),
                    table[:n_full])
        info = {"blocks_copied": n_copy, "blocks_shared": n_shared,
                "tokens": pos, "blocks_total": total}
        if trace_ctx is not None:
            trace_ctx.add_span("kv.adopt", t0_tr,
                               time.perf_counter_ns(), **info)
        flight.record("serving.kv.adopt", rid=req.rid, **info)
        return req, info

    def _refuse_recurrent(self, what):
        if self.window:
            raise WindowCacheUnsupported(
                f"{what} moves the blocks of one pool, and the request's "
                "window layers live in a ring of a second")
        if self.slot_state:
            raise RecurrentStateUnsupported(
                f"{what} moves K/V blocks and would leave the request's "
                f"recurrent state ({', '.join(sorted(self.slot_state))}) "
                "behind")
        if self.kv_row:
            raise LatentCacheUnsupported(
                f"{what} copies K/V blocks by head, which a latent row "
                "has not")

    def step_state(self):
        """The ``step_state`` arrays the programs carry on the device
        (``cache_spec()["step_state"]``: an expert model's load counts),
        fetched to the host: ``{name: array}``, empty for a model that
        names none.  The one place they are read (a decode launch keeps
        its one read-back); to be called between steps.  A decode launch
        in flight is read back first, so that the arrays count what the
        tokens handed out so far were made with; its tokens join the
        events of the step that launched it (the list it returned)."""
        if self._step_spec:
            self._settle(self._events)
        return {name: np.asarray(self.arena.get("state." + name))
                for name in self._step_spec}

    def _adopt_extra(self, slot, req, mig):
        """Subclass hook: rebuild engine-local state the migration
        payload does not carry (the speculative engine re-prefills its
        draft namespace here).  Caller holds ``_cond``."""

    def finish_migrated(self, req):
        """Source-side release after the destination adopted (or the
        fleet abandoned) a migration: finish the held request with
        reason ``"migrated"`` — ``_release_blocks`` donates the
        sequence's blocks to THIS engine's prefix tree (a replayed or
        prefix-sharing prompt re-resolves them here) and drops every
        table reference.  The fleet re-points its stream handle BEFORE
        calling this, so the source-side finish is invisible to the
        consumer."""
        done = self._finish(req, "migrated", [])
        req.tag = None
        return done

    # -- eviction / teardown -------------------------------------------------
    def _release_blocks(self, slot, req, reason):
        """Free a finished request's table: donate the sequence's blocks
        to the prefix tree (when prefill completed cleanly), then drop
        the request's references.  Caller holds ``_cond``."""
        table = self._slot_blocks[slot]
        self._slot_blocks[slot] = None
        st = self._prefill_state.pop(slot, None)
        if self.adapters is not None and req.adapter is not None \
                and self._aid[slot]:
            # drop the request's adapter pin; the tenant stays resident
            # (warm for the next same-tenant request, LRU otherwise)
            self.adapters.release(req.adapter)
        self._write_slot(slot, running=False, bt=0, aid=0, dosample=False,
                         tok=0, pos=0)
        self._held_idle.pop(req.rid, None)
        ent = self._req_host.pop(req.rid, None)
        if ent is not None:
            # released while spilled (cancel / abandoned migration):
            # the host copies die with the request
            for i in ent["idx"]:
                if self._host_tier.pop(("req", req.rid, i)):
                    counters.inc("serving.kv.tier.spill_drops")
        if table is None:
            return
        if self.prefix is not None and st is None and reason != "error" \
                and req.tokens and TRASH_BLOCK not in table:
            # K/V is live through position T + len(tokens) - 2 (the last
            # emitted token was sampled but never written back); a table
            # with trashed (spilled-and-not-restored) entries has holes
            # and cannot donate
            n_avail = int(req.prompt.shape[0]) + len(req.tokens) - 1
            seq = np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int32)])[:n_avail]
            self.prefix.insert(
                self._prefix_key(seq.tolist(), req.adapter), table)
        for b in table:
            if b != TRASH_BLOCK:
                self.pool.release(b)

    def _finish(self, req, reason, events):
        with self._cond:
            slot = req.slot
            done = super()._finish(req, reason, events)
            if done and slot is not None:
                self._release_blocks(slot, req, reason)
        return done

    # -- scheduling ----------------------------------------------------------
    def _sweep(self, events):
        """The lifecycle's sweep; a row it evicts while a decode launch is
        in flight first gets that launch's token, as it would have had the
        launch been read back in the step that made it."""
        flight = self._inflight
        if flight is not None:
            now = time.monotonic()
            if any(r._cancel or (r.deadline is not None and now > r.deadline)
                   for _, r in flight.rows):
                self._settle(events)
        super()._sweep(events)

    def has_work(self):
        """Queued or slotted requests, or a decode launch still to read
        back."""
        return self._inflight is not None or super().has_work()

    def step(self):
        """One scheduler iteration: sweep cancels/deadlines, admit from
        the queue (prefix match + block reservation only — no model
        launches), advance every mid-prefill request by ONE chunk, enqueue
        ONE decode launch for all running slots and read the one before
        it back (its tokens are this step's), re-admit into anything
        freed this step."""
        with span("serving.step") as sp:
            events = []
            with span("serving.sweep"):
                self._sweep(events)
                self._maybe_spill_idle()
            with span("serving.admit"):
                self._admit(events)
            self._prefill_chunks(events)
            self._decode_step(events)
            with span("serving.admit"):
                self._admit(events)
            with span("serving.gauges"):
                if sp.live:   # counted only for someone who is profiling
                    live = self._blocks_live()
                    wlive, whole = self._window_blocks()
                    sp.note(blocks_live=live,
                            blocks_total=self.pool.capacity,
                            kv_live_bytes=(live * self._block_bytes
                                           + wlive * self._wblock_bytes),
                            state_bytes=self._state_bytes)
                    if self.window:
                        sp.note(window_blocks_live=wlive,
                                window_blocks_total=self._ring.size,
                                window_kv_live_bytes=(
                                    wlive * self._wblock_bytes),
                                window_kv_unbounded_bytes=(
                                    whole * self._wblock_bytes))
                counters.set_gauge(
                    "serving.slot_occupancy",
                    sum(r is not None for r in self._slots) / self.max_slots)
                used = self.pool.used_blocks
                counters.set_gauge("serving.kv.blocks_used", used)
                self._observe("serving.kv.block_occupancy",
                              used / max(1, self.pool.capacity))
                if self._host_tier is not None:
                    counters.set_gauge("serving.kv.tier.host_blocks",
                                       self._host_tier.resident)
        self._events = events
        return events

    def _blocks_live(self):
        """Distinct pool blocks in the tables of the requests that hold a
        slot.  ``pool.used_blocks`` also counts what the prefix tree
        retains after a request has finished; this does not."""
        return int(np.count_nonzero(np.bincount(
            self._bt[:, :self.max_blocks].ravel(),
            minlength=1)[TRASH_BLOCK + 1:]))

    def _window_blocks(self):
        """``(ring entries the requests that hold a slot can ever touch,
        blocks they would touch if each kept its whole sequence)``: each
        request's blocks, at most its ring, and all of them; ``(0, 0)``
        for a model without window layers."""
        if not self.window:
            return 0, 0
        need = [self._blocks_needed(int(r.prompt.shape[0]),
                                    r.max_new_tokens)
                for r in self._slots if r is not None]
        return sum(min(b, self.window_entries) for b in need), sum(need)

    def stats(self):
        """The lifecycle's snapshot plus the block-pool / prefix-cache
        fields the Router's fleet aggregation merges (one lock
        acquisition; the RLock makes the nested base call atomic)."""
        with self._cond:
            st = super().stats()
            live = self._blocks_live()
            wlive, _ = self._window_blocks()
            st.update({
                "kv_dtype": self.kv_dtype,
                "kv_kernel": self.kv_kernel,
                "weight_dtype": self.weight_dtype,
                "prefill_programs": len(self._pchunk_jits),
                "block_size": self.pool.block_size,
                "blocks_total": self.pool.capacity,
                "blocks_free": self.pool.free_blocks,
                "blocks_used": self.pool.used_blocks,
                "blocks_live": live,
                "kv_live_bytes": (live * self._block_bytes
                                  + wlive * self._wblock_bytes),
                "state_bytes": self._state_bytes,
                "prefix_cache": self.prefix is not None,
                "block_utilization": (self.pool.used_blocks
                                      / max(1, self.pool.capacity)),
                "prefix_hits": self.kv_prefix_hits,
                "prefix_misses": self.kv_prefix_misses,
                "prefix_hit_tokens": self.kv_prefix_hit_tokens,
                "cow_copies": self.kv_cow_copies,
                "blocks_evicted": self.kv_blocks_evicted,
                "pool_exhausted": self.kv_pool_exhausted_events,
                "prefix_nodes": (0 if self.prefix is None
                                 else self.prefix.nodes),
                "prefilling": len(self._prefill_state),
                "host_tier_capacity": (0 if self._host_tier is None
                                       else self._host_tier.capacity),
                "host_tier_blocks": (0 if self._host_tier is None
                                     else self._host_tier.resident),
                "host_arena_bytes": (0 if self._host_tier is None
                                     else self._host_tier.arena_bytes),
                "tier_spilled": self.kv_tier_spilled,
                "tier_restored": self.kv_tier_restored,
                # per-chip HBM actually held by chip 0's shards — under
                # an mp mesh the KV pools and weight matrices divide by
                # the axis size, the replicated operands do not
                "mesh_tag": self.arena.tag or None,
                "kv_pool_bytes_per_chip": self.arena.device_bytes(
                    "pool_k", "pool_v", "scale_k", "scale_v"),
                "weight_bytes_per_chip": self.arena.device_bytes(
                    "weights"),
                "adapter_slots": self.adapter_slots,
                "adapters": (None if self.adapters is None
                             else self.adapters.stats()),
            })
            if self.window:
                st.update({
                    "window_entries": self.window_entries,
                    "window_blocks_total": self._ring.size,
                    "window_blocks_live": wlive,
                    "window_kv_live_bytes": wlive * self._wblock_bytes,
                })
        return st
