"""Slot-based continuous-batching LLM inference engine.

Iteration-level scheduling (Orca, OSDI '22) over a device-resident KV slot
arena ``[L, max_slots, S_max, nh, hd]``: requests are admitted from a
bounded queue into free slots, decoded TOGETHER one token per step
regardless of arrival time, and evicted on EOS / ``max_new_tokens`` /
deadline / cancellation with the slot immediately rehandable.  All device
work happens in shape-stable donated XLA programs:

* ``prefill(ids[1, Sb], length, key, knobs)`` — one program per
  power-of-two prompt bucket ``Sb`` (pad + causal mask), so steady-state
  serving compiles O(log S_max) prefill programs however many distinct
  prompt lengths arrive.  Returns the request's K/V chunk (zeroed beyond
  ``length``) and its first sampled token.
* ``insert(arena, chunk, slot)`` — ``dynamic_update_slice`` of the chunk
  into the (donated) arena row, clearing the rest of the slot.
* ``decode_step(arena, toks, pos, keys, knobs)`` — ONE program ever:
  every slot advances one token per launch against the donated arena.

Per-slot sampling knobs (temperature / top-k / top-p / greedy) and a
per-slot PRNG key chain seeded per request ride the decode program as
arrays; the sampling math is ``serving.sampling`` — the same transform
``GPT.generate`` traces — and the key-split schedule replicates
``generate``'s exactly, so engine outputs are token-identical to running
each request alone through ``generate``.

The reference analogue is the fused decode serving stack
(fused_multi_transformer + paddlenlp's generation loop); the block/paged
KV ideas follow vLLM (SOSP '23) specialised to TPU-friendly static
shapes: a slot row IS the page, admission IS the allocation.
"""

from __future__ import annotations

import itertools
import threading
import time
import warnings
import weakref
import zlib
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from ..profiler import counters
from ..profiler import devicetime as _devicetime
from ..profiler import flight
from ..profiler import metrics
from ..profiler import trace as rtrace
from ..profiler.host_tracer import span
from .arena import StateArena
from .sampling import next_tokens

# the arena/chunk donations are a no-op on CPU backends; the warning would
# fire on every serving step there
warnings.filterwarnings("ignore",
                        message="Some donated buffers were not usable")

# Per-model cache of the jitted serving programs.  The closures capture
# the MODEL only (never an engine), so every engine over the same model
# instance — fleet replicas, respawned replacements, a paged engine next
# to a slot engine — reuses one set of XLA executables instead of
# recompiling identical programs per engine.  Donation is per-call, and
# jax.jit keys compiled variants by argument shape internally, so
# sharing is invisible except in compile time (and in
# ``serving.retraces``, which only ever counts FEWER traces).
_MODEL_PROGRAMS = weakref.WeakKeyDictionary()


def _model_programs(model):
    try:
        cache = _MODEL_PROGRAMS.get(model)
        if cache is None:
            cache = _MODEL_PROGRAMS[model] = {}
    except TypeError:  # unhashable / non-weakrefable model object
        cache = model.__dict__.setdefault("_serving_programs", {})
    return cache


class EngineBackpressure(RuntimeError):
    """add_request refused: the bounded request queue is full (or, at the
    fleet router, admission was shed).  Carries the structured retry info
    clients need to back off intelligently:

    * ``queue_depth`` — requests waiting at refusal time.
    * ``retry_after_hint`` — estimated seconds until the backlog drains
      (``outstanding_tokens / decode tokens/s EMA``), or None when the
      engine has produced no throughput estimate yet.
    """

    def __init__(self, msg="", queue_depth=0, retry_after_hint=None):
        super().__init__(msg)
        self.queue_depth = int(queue_depth)
        self.retry_after_hint = retry_after_hint


class EngineClosed(RuntimeError):
    """add_request refused: the engine is draining or drained."""


class RecurrentStateUnsupported(RuntimeError):
    """Refused for a model with recurrent layers: the feature moves or
    adopts K/V blocks, and a request that arrived somewhere without the
    fixed-size state of its recurrent layers would be served silently
    wrong.  Raised at construction for ``kv_layout="slots"``,
    ``kv_dtype=``, ``host_kv_blocks=``, ``adapter_slots=``, ``mesh=`` and
    ``draft_model=``, and by ``export_request`` / ``adopt_migration``
    (the prefix cache resolves to off instead: reuse is an optimisation,
    not a request)."""


class Request:
    """One generation request and its live state (also the user handle:
    ``add_request`` returns it; iterate it to stream tokens)."""

    __slots__ = ("rid", "prompt", "max_new_tokens", "do_sample",
                 "temperature", "top_k", "top_p", "eos_token_id", "seed",
                 "state", "finish_reason", "tokens", "slot", "arrival_ns",
                 "last_emit_ns", "deadline", "_cancel", "_engine", "error",
                 "tag", "trace", "hold", "adapter")

    def __init__(self, rid, prompt, max_new_tokens, do_sample, temperature,
                 top_k, top_p, eos_token_id, seed, deadline, engine):
        self.rid = rid
        self.prompt = prompt                    # np.int32 [T]
        self.max_new_tokens = max_new_tokens
        self.do_sample = do_sample
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_token_id = eos_token_id
        self.seed = seed
        self.state = "queued"     # queued | running | finished
        # eos | length | deadline | cancelled | error
        self.finish_reason = None
        self.error = None         # the exception, when finish_reason="error"
        self.tokens = []          # generated tokens (includes eos if hit)
        self.slot = None
        # perf_counter_ns: the clock of spans, request traces and histograms
        self.arrival_ns = time.perf_counter_ns()
        self.last_emit_ns = None  # perf_counter_ns of the last emitted token
        self.deadline = deadline  # absolute time.monotonic() or None
        self._cancel = False
        self._engine = engine
        self.tag = None           # opaque owner backref (fleet router)
        self.trace = None         # TraceContext when request tracing is on
        self.hold = False         # park after prefill for KV migration
        self.adapter = None       # tenant id (LoRA adapter), None = base

    @property
    def is_finished(self):
        return self.state == "finished"

    def cancel(self):
        """Request cancellation; the engine evicts the request (or drops
        it from the queue) on its next step.  Safe to call from any
        thread, any number of times, including after the request finished
        (the finish CAS in ``LLMEngine._finish`` makes the late cancel a
        no-op — it can never double-release the slot)."""
        self._cancel = True

    def output_ids(self):
        """prompt + generated tokens, as one np.int32 array."""
        return np.concatenate([self.prompt,
                               np.asarray(self.tokens, np.int32)])

    def __iter__(self):
        """Stream generated tokens, pumping the engine while this request
        is live (single-threaded serving loop)."""
        i = 0
        while True:
            while i < len(self.tokens):
                yield self.tokens[i]
                i += 1
            if self.is_finished:
                return
            self._engine.step()

    def __repr__(self):
        return (f"Request(id={self.rid}, state={self.state!r}, "
                f"reason={self.finish_reason!r}, "
                f"generated={len(self.tokens)})")


def bucket_length(n, min_bucket=8, max_len=None):
    """Smallest power-of-two >= n (floored at ``min_bucket``, clamped to
    ``max_len``): the prefill program shape for an n-token prompt."""
    b = max(int(min_bucket), 1)
    while b < n:
        b *= 2
    return min(b, max_len) if max_len is not None else b


class LLMEngine:
    """Continuous-batching engine over one ``GPTForCausalLM``.

    ``add_request()`` enqueues (bounded queue, optional blocking
    backpressure); ``step()`` admits into free slots, runs one decode
    launch for every active slot, and evicts finished rows; ``generate()``
    is the blocking convenience loop; iterating a returned ``Request``
    streams its tokens.  ``drain()`` stops admission and finishes all
    outstanding work.
    """

    def __new__(cls, *args, **kw):
        # kv_layout="paged" routes construction to the paged subclass so
        # `LLMEngine(model, kv_layout="paged")` is the one public spelling
        # (serving.paged imports this module; resolve lazily); a
        # draft_model= routes further to the speculative engine, which
        # runs over the paged arena
        if cls is LLMEngine and kw.get("draft_model") is not None:
            from .speculative import SpeculativeLLMEngine
            return super().__new__(SpeculativeLLMEngine)
        if cls is LLMEngine and kw.get("kv_layout", "slots") == "paged":
            from .paged import PagedLLMEngine
            return super().__new__(PagedLLMEngine)
        return super().__new__(cls)

    def __init__(self, model, max_slots=8, max_seq_len=None, queue_size=64,
                 min_bucket=8, eos_token_id=None, kv_layout="slots",
                 block_size=16, n_blocks=None, prefill_chunk=None,
                 prefix_cache=True, kv_dtype=None, weight_dtype=None,
                 host_kv_blocks=0, spill_idle_steps=0, mesh=None,
                 shard_rules=None, adapter_slots=0, adapter_rank=8,
                 tenant_buckets=8):
        if kv_layout not in ("slots", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}; "
                             "want 'slots' or 'paged'")
        if kv_dtype not in (None, "int8", "fp8"):
            raise ValueError(f"kv_dtype must be None, 'int8' or 'fp8', "
                             f"got {kv_dtype!r}")
        if kv_dtype is not None and kv_layout != "paged":
            raise ValueError("kv_dtype requires kv_layout='paged' (the "
                             "slot arena is not quantized)")
        if weight_dtype not in (None, "int8"):
            raise ValueError(f"weight_dtype must be None or 'int8', "
                             f"got {weight_dtype!r}")
        if int(adapter_slots or 0) > 0 and kv_layout != "paged":
            raise ValueError("adapter_slots requires kv_layout='paged' "
                             "(adapter ids ride the paged dispatches)")
        self.kv_layout = kv_layout
        # multi-tenant LoRA knobs (paged engine only; 0 disables).
        # tenant_buckets bounds the per-tenant telemetry cardinality:
        # TTFT/ITL histograms are keyed by a stable hash bucket, never by
        # raw tenant id.
        self.adapter_slots = int(adapter_slots or 0)
        self.adapter_rank = int(adapter_rank)
        self.tenant_buckets = int(tenant_buckets)
        # paged-arena knobs (used by the PagedLLMEngine _init_kv override;
        # inert under the default slot layout)
        self.block_size = int(block_size)
        self.n_blocks = n_blocks
        self.prefill_chunk = prefill_chunk
        self.prefix_caching = bool(prefix_cache)
        self.kv_dtype = kv_dtype
        self.weight_dtype = weight_dtype
        # host-RAM KV tier knobs (paged engine only; 0 disables)
        self.host_kv_blocks = int(host_kv_blocks or 0)
        self.spill_idle_steps = int(spill_idle_steps or 0)
        c = model.config
        self.model = model
        self.config = c
        # what the model caches: paged K/V for ``kv_layers`` layers, and
        # one row per slot of each ``slot_state`` array (recurrent layers)
        cache = model.cache_spec()
        self.kv_layers = int(cache["kv_layers"])
        self.slot_state = dict(cache["slot_state"])
        if self.slot_state:
            asked = {"kv_layout='slots'": kv_layout != "paged",
                     "kv_dtype=": kv_dtype is not None,
                     "host_kv_blocks=": self.host_kv_blocks > 0,
                     "adapter_slots=": self.adapter_slots > 0,
                     "mesh=": mesh is not None}
            if any(asked.values()):
                raise RecurrentStateUnsupported(
                    f"{type(model).__name__} keeps recurrent state per "
                    "request, which "
                    + ", ".join(k for k, v in asked.items() if v)
                    + " cannot carry yet")
            # a prefix hit adopts K/V blocks and would skip the tokens
            # that built the recurrent state
            self.prefix_caching = False
        self.max_slots = int(max_slots)
        self.max_seq_len = int(max_seq_len or c.max_seq_len)
        if not c.use_rope and self.max_seq_len > c.max_seq_len:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the model's "
                f"learned-position table ({c.max_seq_len})")
        self.queue_size = int(queue_size)
        self.min_bucket = int(min_bucket)
        self.eos_token_id = eos_token_id  # default for requests
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

            B, S = self.max_slots, self.max_seq_len
            nh, hd = int(cache["kv_heads"]), int(cache["head_dim"])
            dt = jnp.dtype(c.dtype)
            self._init_kv(c, B, S, nh, hd, dt)

        # host mirrors of the per-slot decode inputs
        key_size = jax.random.key_data(jax.random.key(0)).shape[0]
        self._tok = np.zeros(B, np.int32)
        self._pos = np.zeros(B, np.int32)
        self._keys = np.zeros((B, key_size), np.uint32)
        self._temp = np.ones(B, np.float32)
        self._topk = np.zeros(B, np.int32)
        self._topp = np.ones(B, np.float32)
        self._dosample = np.zeros(B, np.bool_)

        self._slots: list = [None] * B
        self._free = list(range(B - 1, -1, -1))  # slot 0 handed out first
        self._queue: deque = deque()
        # ONE engine lock: the Condition's (re-entrant) lock guards the
        # queue, slot bookkeeping, the finish CAS, and the stats()
        # aggregates below — stats() is a single-acquisition snapshot
        self._cond = threading.Condition(threading.RLock())
        self._closed = False
        self._rid = itertools.count()
        self._outstanding = 0     # undelivered tokens across queued+active
        self._tps_ema = 0.0       # decode tokens/s, EMA over launches
        self._ema_alpha = 0.25

        self._prefill_jits = {}   # bucket -> jitted prefill
        self._insert_jits = {}    # bucket -> jitted insert
        self._decode_jit = None
        self._captured = set()    # program names already sent to telemetry

        # per-engine mergeable latency/occupancy histograms — the fleet
        # Router merges these across replicas for fleet-wide percentiles;
        # every observation also feeds the process-global registry under
        # the same serving.* name
        self.hists = {
            n: metrics.Histogram(n, unit)
            for n, unit in (("serving.ttft_ns", "ns"),
                            ("serving.itl_ns", "ns"),
                            ("serving.queue_wait_ns", "ns"),
                            ("serving.prefill_occupancy", "frac"),
                            ("serving.decode_occupancy", "frac"))}

    def _observe(self, name, value, sum_counter=False):
        metrics.observe(name, value, sum_counter=sum_counter,
                        extra=self.hists[name])

    def _tenant_bucket(self, tenant):
        """Stable low-cardinality label for per-tenant isolation
        telemetry: ``"base"`` for un-adapted rows, else a crc32 hash
        bucket so thousands of tenants fold into ``tenant_buckets``
        histogram keys."""
        if tenant is None:
            return "base"
        return f"t{zlib.crc32(str(tenant).encode()) % self.tenant_buckets}"

    def _observe_tenant(self, base, tenant, value):
        """Record a latency sample into the tenant-bucketed histogram
        (created lazily — only buckets that actually serve traffic
        exist).  Feeds the global registry too, so the health plane's
        ``noisy_neighbor`` watchdog sees the same windows."""
        name = f"{base}.tenant.{self._tenant_bucket(tenant)}"
        h = self.hists.get(name)
        if h is None:
            h = self.hists[name] = metrics.Histogram(name, "ns")
        metrics.observe(name, value, extra=h)

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

    def histogram_snapshot(self):
        """Copies of the per-engine histograms (point-in-time, safe to
        ``Histogram.merge`` across replicas — the fleet Router does)."""
        return {n: h.copy() for n, h in self.hists.items()}

    def _init_kv(self, c, B, S, nh, hd, dt):
        """Allocate the device KV storage: the slot arena here, a block
        pool in the PagedLLMEngine override.  Declared through the
        StateArena so the head axis shards over ``mp`` when a mesh is
        set (``[L, B, S, nh/mp, hd]``)."""
        from .arena import KV_POOL_SPEC
        self.arena.declare("slot_k",
                           jnp.zeros((self.kv_layers, B, S, nh, hd), dt),
                           spec=KV_POOL_SPEC)
        self.arena.declare("slot_v",
                           jnp.zeros((self.kv_layers, B, S, nh, hd), dt),
                           spec=KV_POOL_SPEC)

    # the slot arena lives in the StateArena; donated-program outputs are
    # rebound through the setters so every rebind site inherits the spec
    @property
    def _ck(self):
        return self.arena.get("slot_k")

    @_ck.setter
    def _ck(self, v):
        self.arena.bind("slot_k", v)

    @property
    def _cv(self):
        return self.arena.get("slot_v")

    @_cv.setter
    def _cv(self, v):
        self.arena.bind("slot_v", v)

    def release_kv(self):
        """Drop the device KV storage (a dead replica's arena is garbage
        — the fleet frees its HBM before respawning)."""
        self._ck = self._cv = None

    def prefix_peek(self, prompt, tenant=None):
        """Tokens of ``prompt`` a prefix cache could serve without
        prefilling — 0 under the slot layout (no sharing), overridden by
        the paged engine.  The Router uses this for prefix-hit-aware
        dispatch.  ``tenant`` scopes the probe to that adapter's KV
        plane (KV computed under a LoRA adapter never matches base)."""
        return 0

    def prefix_probe(self, prompt, tenant=None):
        """``(device_tokens, host_tokens)`` a prefix cache could serve —
        ``(0, 0)`` under the slot layout; the paged engine overrides.
        The Router's cost model discounts the host component by the
        restore price (see ``serving.router``).  ``tenant`` scopes the
        probe to that adapter's KV plane."""
        return 0, 0

    def adapter_peek(self, tenant):
        """Tokens of prefill-equivalent work saved because ``tenant``'s
        LoRA factors are already resident in this replica's adapter
        arena — 0 here (the slot engine serves no adapters), overridden
        by the paged engine.  The Router folds this into the same cost
        model as ``prefix_peek`` for tenant-affine dispatch."""
        return 0

    # -- compiled programs ---------------------------------------------------
    @staticmethod
    def _first_token(logits, key_data, do_sample, temp, top_k, top_p):
        """The prefill's first token from ``logits[1, V]``: the shared
        sampling tail over a batch of one (identical key discipline and
        math to generate's post-prefill draw)."""
        nxt, new_keys = next_tokens(
            logits, key_data[None],
            *(jnp.reshape(x, (1,)) for x in (do_sample, temp, top_k, top_p)))
        return nxt[0], new_keys[0]

    def _prefill_for(self, bucket):
        fn = self._prefill_jits.get(bucket)
        if fn is None:
            model = self.model

            def build():
                def prefill(w, ids, length, key_data, do_sample, temp,
                            top_k, top_p):
                    counters.inc("serving.retraces")  # trace-time only
                    ck, cv, logits = model.prefill_slot(w, ids, length)
                    tok, new_key = LLMEngine._first_token(
                        logits, key_data, do_sample, temp, top_k, top_p)
                    return ck, cv, tok, new_key
                return jax.jit(prefill)
            key = self.arena.decorate("prefill_slot")
            with span("serving.program_build", level=0, key=key,
                      bucket=bucket):
                fn = self.arena.program(_model_programs(model), key, build)
            self._prefill_jits[bucket] = fn
            counters.set_gauge("serving.prefill_programs",
                               len(self._prefill_jits))
        return fn

    def _insert_for(self, bucket):
        fn = self._insert_jits.get(bucket)
        if fn is None:
            L = self.kv_layers
            nh = self.config.num_heads
            hd = self.config.hidden_size // nh
            S = self.max_seq_len
            key = (self.arena.decorate("insert_slot"), S)

            def build():
                def insert(ck, cv, kc, vc, slot):
                    counters.inc("serving.retraces")
                    zk = jnp.zeros((L, 1, S, nh, hd), kc.dtype)
                    zv = jnp.zeros((L, 1, S, nh, hd), vc.dtype)
                    zk = jax.lax.dynamic_update_slice(zk, kc,
                                                      (0, 0, 0, 0, 0))
                    zv = jax.lax.dynamic_update_slice(zv, vc,
                                                      (0, 0, 0, 0, 0))
                    ck = jax.lax.dynamic_update_slice(ck, zk,
                                                      (0, slot, 0, 0, 0))
                    cv = jax.lax.dynamic_update_slice(cv, zv,
                                                      (0, slot, 0, 0, 0))
                    return ck, cv
                return jax.jit(insert, donate_argnums=(0, 1))
            fn = self.arena.program(_model_programs(self.model), key, build)
            self._insert_jits[bucket] = fn
        return fn

    def _decode(self):
        if self._decode_jit is None:
            model = self.model

            def build():
                def decode(w, ck, cv, tok, pos, keys_data, do_sample, temp,
                           top_k, top_p):
                    counters.inc("serving.retraces")
                    logits, ck, cv = model.decode_slots(w, tok, pos, ck, cv)
                    nxt, new_keys = next_tokens(
                        logits, keys_data, do_sample, temp, top_k, top_p)
                    return nxt, ck, cv, new_keys
                return jax.jit(decode, donate_argnums=(1, 2))
            key = self.arena.decorate("decode_slots")
            with span("serving.program_build", level=0, key=key):
                self._decode_jit = self.arena.program(
                    _model_programs(model), key, build)
        return self._decode_jit

    # -- request intake ------------------------------------------------------
    def add_request(self, prompt, max_new_tokens=32, do_sample=False,
                    temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                    seed=None, deadline_s=None, block=True, timeout=None,
                    trace_ctx=None, hold_after_prefill=False, adapter=None):
        """Enqueue one prompt; returns the live ``Request`` handle.

        Backpressure: when the bounded queue is full, ``block=False``
        raises ``EngineBackpressure`` immediately; ``block=True`` waits up
        to ``timeout`` seconds (forever if None) for another thread's
        ``step()`` to make room, then raises.  ``deadline_s`` is a
        per-request wall-clock budget (queue wait included); on expiry the
        request finishes with ``finish_reason='deadline'`` and whatever
        tokens it produced.  ``trace_ctx`` carries a caller-minted
        ``TraceContext`` (the fleet threads the SAME context through
        every retry attempt); with tracing sampled on and no context
        given, the engine mints its own.  ``hold_after_prefill`` parks the
        request after its last prefill chunk (state ``"held"``) instead of
        entering decode, emitting a ``{"type": "prefilled"}`` event — the
        disaggregated fleet's hand-off point for KV migration to a decode
        replica.  Honored by the paged engine; slot-layout engines decode
        in place (there is no block table to migrate).  ``adapter`` names
        the tenant whose registered LoRA factors decorate this request's
        matmuls (None = base model); requires an engine built with
        ``adapter_slots > 0``."""
        if self._closed:
            raise EngineClosed("engine is drained; no new requests")
        if adapter is not None and not self.adapter_slots:
            raise ValueError("adapter given but the engine was built "
                             "with adapter_slots=0")
        ids = np.asarray(
            prompt._data if hasattr(prompt, "_data") else prompt,
            dtype=np.int32).reshape(-1)
        T = int(ids.shape[0])
        if T < 1:
            raise ValueError("empty prompt")
        if T + int(max_new_tokens) > self.max_seq_len:
            raise ValueError(
                f"prompt ({T}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"the engine's max_seq_len ({self.max_seq_len})")
        eos = eos_token_id if eos_token_id is not None else self.eos_token_id
        if seed is None:
            seed = int(np.random.randint(0, 2**31 - 1))
        deadline = (time.monotonic() + float(deadline_s)
                    if deadline_s is not None else None)
        req = Request(next(self._rid), ids, int(max_new_tokens),
                      bool(do_sample), float(temperature), int(top_k),
                      float(top_p), (None if eos is None else int(eos)),
                      int(seed), deadline, self)
        req.hold = bool(hold_after_prefill)
        req.adapter = adapter
        req.trace = trace_ctx if trace_ctx is not None \
            else rtrace.new_trace(req.rid)
        if req.trace is not None:
            req.trace.stamp("enqueue")  # queue span spans wait + queue time
        with self._cond:
            while len(self._queue) >= self.queue_size:
                if not block:
                    raise EngineBackpressure(
                        f"request queue full ({self.queue_size})",
                        queue_depth=len(self._queue),
                        retry_after_hint=self._retry_hint_locked())
                if not self._cond.wait(timeout):
                    raise EngineBackpressure(
                        f"request queue full ({self.queue_size}); timed "
                        f"out after {timeout}s",
                        queue_depth=len(self._queue),
                        retry_after_hint=self._retry_hint_locked())
                if self._closed:
                    raise EngineClosed("engine drained while waiting")
            self._queue.append(req)
            self._outstanding += req.max_new_tokens
        counters.inc("serving.requests")
        flight.record("serving.request", rid=req.rid, prompt_len=T,
                      max_new_tokens=req.max_new_tokens)
        return req

    def _note_decode(self, emitted, elapsed_s):
        """Fold one decode launch into the tokens/s EMA.  ``emitted`` is
        the number of tokens the launch actually DELIVERED — one per
        active slot for plain decode, up to K+1 per slot for a
        speculative verify round — never the dispatch count, so Router
        SLO shedding and least-loaded dispatch
        (``backlog / decode_tps_ema``) stay correct whatever the
        per-dispatch token yield."""
        inst = emitted / max(elapsed_s, 1e-9)
        with self._cond:
            self._tps_ema = (inst if self._tps_ema <= 0 else
                             self._ema_alpha * inst
                             + (1 - self._ema_alpha) * self._tps_ema)

    def _retry_hint_locked(self):
        """Seconds until the current backlog drains at the EMA decode
        rate; None before the first decode launch.  Caller holds _cond."""
        if self._tps_ema <= 0:
            return None
        return self._outstanding / self._tps_ema

    # -- scheduling ----------------------------------------------------------
    def _finish(self, req, reason, events):
        """Terminal transition.  Thread-safe compare-and-set on the
        request state under the engine lock: the fleet router cancels /
        reaps from a different thread than the replica's step() loop, and
        a double finish must not fire twice or double-release the slot."""
        with self._cond:
            if req.state == "finished":
                return False
            req.state = "finished"
            req.finish_reason = reason
            self._outstanding -= max(
                0, req.max_new_tokens - len(req.tokens))
            if req.slot is not None:
                s = req.slot
                self._slots[s] = None
                self._free.append(s)
                self._dosample[s] = False
                self._tok[s] = 0
                self._pos[s] = 0
                req.slot = None
        counters.inc("serving.evictions")
        counters.inc(f"serving.evictions.{reason}")
        flight.record("serving.finish", rid=req.rid, reason=reason,
                      tokens=len(req.tokens))
        events.append({"type": "finished", "request": req, "reason": reason})
        tr = req.trace
        if tr is not None:
            tr.add_event("evict", reason=reason)
            if req.tag is None:
                # standalone request: the engine owns trace finalization;
                # fleet-owned requests (tag set) are finalized by
                # FleetRequest._finish, which sees retries/redispatches
                breached = (req.deadline is not None
                            and time.monotonic() > req.deadline)
                rtrace.finish(tr, reason, breached=breached)
        return True

    def _sweep(self, events):
        """Evict cancelled / past-deadline requests — active slots AND the
        admission queue, so a request whose deadline lapsed while queued is
        evicted here instead of spending a prefill launch in ``_admit``."""
        now = time.monotonic()
        for req in list(self._slots):
            if req is None:
                continue
            if req._cancel:
                self._finish(req, "cancelled", events)
            elif req.deadline is not None and now > req.deadline:
                self._finish(req, "deadline", events)
        expired = []
        with self._cond:
            dead = [r for r in self._queue
                    if r._cancel or (r.deadline is not None
                                     and now > r.deadline)]
            if dead:
                for r in dead:
                    self._queue.remove(r)
                expired = dead
                self._cond.notify_all()
        for req in expired:
            if req._cancel:
                self._finish(req, "cancelled", events)
            else:
                counters.inc("serving.deadline_expired")
                self._finish(req, "deadline", events)

    def _emit(self, req, tok, events):
        """Record one generated token; finish on EOS / length.  The event
        carries the token's stream index, stamped HERE where it is
        synchronous — consumers that batch events per step (the fleet's
        replay prefix check) see ``req.tokens`` already advanced past this
        token when one step emits several (prefill + same-step decode)."""
        req.tokens.append(int(tok))
        now_ns = time.perf_counter_ns()
        if len(req.tokens) == 1:
            self._observe("serving.ttft_ns", now_ns - req.arrival_ns)
            if self.adapter_slots:
                self._observe_tenant("serving.ttft_ns", req.adapter,
                                     now_ns - req.arrival_ns)
        elif req.last_emit_ns is not None:
            self._observe("serving.itl_ns", now_ns - req.last_emit_ns)
            if self.adapter_slots:
                self._observe_tenant("serving.itl_ns", req.adapter,
                                     now_ns - req.last_emit_ns)
        req.last_emit_ns = now_ns
        with self._cond:
            self._outstanding -= 1
        events.append({"type": "token", "request": req, "token": int(tok),
                       "index": len(req.tokens) - 1})
        if req.eos_token_id is not None and int(tok) == req.eos_token_id:
            self._finish(req, "eos", events)
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish(req, "length", events)

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
            self._observe("serving.queue_wait_ns",
                          time.perf_counter_ns() - req.arrival_ns,
                          sum_counter=True)
            tr = req.trace
            if tr is not None:
                tr.span_from("enqueue", "queue")
            slot = self._free.pop()
            t0_tr = time.perf_counter_ns() if tr is not None else 0
            try:
                from ..resilience import faultinject as _fi
                _fi.maybe_fault("serving_prefill", req.rid)
                T = int(req.prompt.shape[0])
                bucket = bucket_length(T, self.min_bucket, self.max_seq_len)
                self._observe("serving.prefill_occupancy", T / bucket)
                with span("serving.prefill.operands"):
                    ids = np.zeros((1, bucket), np.int32)
                    ids[0, :T] = req.prompt
                    key_data = np.asarray(
                        jax.random.key_data(jax.random.key(req.seed)))
                    pf = self._prefill_for(bucket)
                    pname = self.arena.decorate(f"serving.prefill[b{bucket}]")
                    iname = self.arena.decorate(f"serving.insert[b{bucket}]")
                    pargs = (self._w, self.arena.operand(ids), np.int32(T),
                             key_data, np.bool_(req.do_sample),
                             np.float32(req.temperature),
                             np.int32(req.top_k), np.float32(req.top_p))
                    self._maybe_capture(pname, pf, *pargs)
                    self._maybe_audit(pname, pf, *pargs)
                with span("serving.prefill.dispatch"):
                    _dt = _devicetime.note(pname)
                    kc, vc, tok, new_key = pf(*pargs)
                    _devicetime.observe(_dt, (kc, vc, tok))
                    ins = self._insert_for(bucket)
                    self._maybe_capture(iname, ins,
                                        self._ck, self._cv, kc, vc,
                                        np.int32(slot))
                    self._maybe_audit(iname, ins,
                                      self._ck, self._cv, kc, vc,
                                      np.int32(slot), donate_argnums=(0, 1))
                    _dt = _devicetime.note(iname)
                    self._ck, self._cv = ins(
                        self._ck, self._cv, kc, vc, np.int32(slot))
                    _devicetime.observe(_dt, (self._ck, self._cv))
                if tr is not None:
                    tr.add_span("prefill", t0_tr, time.perf_counter_ns(),
                                bucket=bucket, tokens=T)
            except Exception as e:
                # a poisoned request (bad prompt, injected fault, prefill
                # blow-up) must not kill the engine loop: contain it to
                # finish_reason="error" and hand the slot right back
                self._free.append(slot)
                req.error = e
                counters.inc("serving.request_errors")
                self._finish(req, "error", events)
                continue
            counters.inc("serving.prefill_batches")
            req.state = "running"
            req.slot = slot
            self._slots[slot] = req
            with span("serving.prefill.wait"):    # the prefill's read-back
                self._tok[slot] = int(tok)
                self._pos[slot] = T
                self._keys[slot] = np.asarray(new_key)
            self._temp[slot] = req.temperature
            self._topk[slot] = req.top_k
            self._topp[slot] = req.top_p
            self._dosample[slot] = req.do_sample
            events.append({"type": "admitted", "request": req})
            self._emit(req, int(tok), events)

    def _decode_step(self, events):
        active = [(s, r) for s, r in enumerate(self._slots) if r is not None]
        if not active:
            return
        self._observe("serving.decode_occupancy",
                      len(active) / self.max_slots)
        t0 = time.perf_counter()
        tr_on = rtrace.enabled()
        t0_tr = time.perf_counter_ns() if tr_on else 0
        with span("serving.decode.operands"):
            dec = self._decode()
            op = self.arena.operand
            dname = self.arena.decorate("serving.decode")
            dargs = (self._w, self._ck, self._cv,
                     op(self._tok), op(self._pos),
                     op(self._keys), op(self._dosample),
                     op(self._temp), op(self._topk),
                     op(self._topp))
            self._maybe_capture(dname, dec, *dargs)
            self._maybe_audit(dname, dec, *dargs,
                              donate_argnums=(1, 2))
        with span("serving.decode.dispatch"):
            _dt = _devicetime.note(dname)
            nxt, self._ck, self._cv, new_keys = dec(*dargs)
            _devicetime.observe(_dt, nxt)
        with span("serving.decode.wait"):
            nxt = np.asarray(nxt)
        if tr_on:
            t1_tr = time.perf_counter_ns()
            for _s, r in active:
                if r.trace is not None:
                    r.trace.add_span("decode.iter", t0_tr, t1_tr,
                                     batch=len(active))
        with span("serving.decode.wait"):    # the second read-back
            self._keys = np.array(new_keys)  # mutable host copy
        # one token emitted per active slot this launch
        self._note_decode(len(active), time.perf_counter() - t0)
        counters.inc("serving.decode_steps")
        # every slot that holds a request runs here, and _finish clears a
        # freed slot's flag: the array as it stands is the running rows'
        counters.inc("serving.decode.sampled_steps",
                     int(self._dosample.any()))
        counters.inc("serving.decode_tokens", len(active))
        with span("serving.decode.emit"):
            for s, req in active:
                self._tok[s] = nxt[s]
                self._pos[s] += 1
                self._emit(req, nxt[s], events)

    def step(self):
        """One scheduler iteration: sweep cancels/deadlines, admit from
        the queue into free slots (prefill + arena insert), run ONE decode
        launch for all active slots, re-admit into slots evicted this
        step.  Returns the list of events ({'type': 'admitted' | 'token' |
        'finished', ...}) produced."""
        with span("serving.step"):
            events = []
            with span("serving.sweep"):
                self._sweep(events)
            with span("serving.admit"):
                self._admit(events)
            self._decode_step(events)
            with span("serving.admit"):
                self._admit(events)  # freed slots are immediately rehandable
        counters.set_gauge(
            "serving.slot_occupancy",
            sum(r is not None for r in self._slots) / self.max_slots)
        return events

    # -- conveniences --------------------------------------------------------
    def has_work(self):
        with self._cond:
            queued = len(self._queue)
        return queued > 0 or any(r is not None for r in self._slots)

    def generate(self, prompts, **kw):
        """Blocking batch API: submit every prompt, step until all finish,
        return their full sequences (prompt + generated) as np.int32
        arrays.  Oversubscription beyond the queue bound is handled by
        stepping the engine between submissions."""
        pending = deque(prompts)
        handles = []
        while pending or not all(h.is_finished for h in handles):
            while pending:
                try:
                    handles.append(self.add_request(pending[0], block=False,
                                                    **kw))
                    pending.popleft()
                except EngineBackpressure:
                    break
            self.step()
        return [h.output_ids() for h in handles]

    def drain(self):
        """Graceful shutdown: stop admitting (``add_request`` raises
        ``EngineClosed``), finish every queued + active request, return
        them.  Idempotent.  Queued requests that are cancelled or already
        past their deadline are swept up front (``serving.deadline_expired``)
        — drain never spends a prefill launch on work that can no longer
        meet its budget."""
        self._closed = True
        with self._cond:
            self._cond.notify_all()
        events = []
        self._sweep(events)
        done = [ev["request"] for ev in events if ev["type"] == "finished"]
        while self.has_work():
            for ev in self.step():
                if ev["type"] == "finished":
                    done.append(ev["request"])
        return done

    def stats(self):
        """Atomic snapshot under ONE lock acquisition — the fleet router
        reads this from other threads to make dispatch/shedding decisions,
        so the fields must be mutually consistent, never torn.

        ``outstanding_tokens`` is the undelivered-token backlog (sum of
        remaining ``max_new_tokens`` over queued + active requests);
        ``decode_tps_ema`` is the decode tokens/s EMA over launches
        (0.0 before the first decode)."""
        with self._cond:
            return {
                "kv_layout": self.kv_layout,
                "active": sum(r is not None for r in self._slots),
                "queued": len(self._queue),
                "free_slots": len(self._free),
                "max_slots": self.max_slots,
                "prefill_programs": len(self._prefill_jits),
                "closed": self._closed,
                "outstanding_tokens": self._outstanding,
                "decode_tps_ema": self._tps_ema,
            }
