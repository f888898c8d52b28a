"""Request lifecycle of the serving engine: what happens to a request
between ``add_request`` and its last token, whatever the cache holds.

Iteration-level scheduling (Orca, OSDI '22): requests wait in a bounded
queue, are admitted into one of ``max_slots`` rows, decoded TOGETHER one
token per step regardless of arrival time, and evicted on EOS /
``max_new_tokens`` / deadline / cancellation with the row immediately
rehandable.  This module holds that half and nothing of the device: the
``Request`` handle, the refusals (``EngineBackpressure``,
``EngineClosed``, ``RecurrentStateUnsupported``,
``LatentCacheUnsupported``, ``WindowCacheUnsupported``,
``BlockDecodeUnsupported``), and
``_RequestLifecycle`` — the bounded queue and ``add_request``, the finish
compare-and-set, the sweep of cancelled and late requests, token
emission with its TTFT / ITL histograms, ``generate`` / ``drain`` and
the base of ``stats()``.

``serving.paged.LLMEngine`` is the engine: it adds the paged K/V pool,
the compiled programs and ``step()``; ``serving.speculative`` adds
draft/verify on top of that.  The imports point one way (lifecycle <-
cache and programs <- speculation): this module imports neither.
"""

from __future__ import annotations

import itertools
import threading
import time
import zlib
from collections import deque

import numpy as np

from ..profiler import counters
from ..profiler import flight
from ..profiler import metrics
from ..profiler import trace as rtrace


def __getattr__(name):
    # ``benchmark/tests/test_faults.py`` patches
    # ``serving.engine.LLMEngine._emit``, and a simplicity PR edits nothing
    # under ``benchmark/``: the old address of the public class resolves,
    # on demand, to where it lives now (ROADMAP D16 deletes this)
    if name == "LLMEngine":
        from .paged import LLMEngine
        return LLMEngine
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


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
    wrong.  Raised at construction for ``kv_dtype=``,
    ``host_kv_blocks=``, ``adapter_slots=``, ``mesh=`` and
    ``draft_model=``, and by ``export_request`` / ``adopt_migration``
    (the prefix cache resolves to off instead: reuse is an optimisation,
    not a request)."""


class LatentCacheUnsupported(RuntimeError):
    """Refused for a model whose cache is one latent row per token and
    layer with no head axis (``cache_spec()["kv_row"]``): the feature
    stores, shards, copies or rolls back K/V by head, and would serve a
    latent pool silently wrong.  Raised at construction for ``kv_dtype=``,
    ``host_kv_blocks=``, ``adapter_slots=``, ``mesh=`` and
    ``draft_model=``, and by ``export_request`` / ``adopt_migration``
    (the prefix cache resolves to off instead: its copy-on-write clone
    copies blocks by head)."""


class WindowCacheUnsupported(RuntimeError):
    """Refused for a model whose window layers keep only the last positions
    of a row (``cache_spec()["window"]``): the engine holds them in a second
    pool whose blocks a row reuses as a ring, and the feature stores,
    shards, copies or adopts K/V blocks of one pool, and would serve such
    a model silently wrong.  Raised at construction for ``kv_dtype=``,
    ``host_kv_blocks=``, ``adapter_slots=``, ``mesh=`` and
    ``draft_model=``, and by ``export_request`` / ``adopt_migration``
    (the prefix cache resolves to off instead: a hit would adopt blocks of
    the full layers only)."""


class BlockDecodeUnsupported(RuntimeError):
    """Refused for a model that decodes by blocks
    (``cache_spec()["decode_block"]``: a launch is one denoising pass over
    a block of positions a row, and a row's K/V enter the pool only when
    its block commits): the feature assumes one token and one cached
    position a row and launch, and would serve such a model silently
    wrong.  Raised at construction for ``draft_model=``, ``kv_dtype=``,
    ``host_kv_blocks=``, ``adapter_slots=`` and ``mesh=``, by
    ``add_request(hold_after_prefill=True)`` and by ``export_request`` /
    ``adopt_migration`` (the prefix cache resolves to off instead: a hit
    would have to end on a whole block)."""


class Request:
    """One generation request and its live state (also the user handle:
    ``add_request`` returns it; iterate it to stream tokens)."""

    __slots__ = ("rid", "prompt", "max_new_tokens", "do_sample",
                 "temperature", "top_k", "top_p", "eos_token_id", "seed",
                 "state", "finish_reason", "tokens", "slot", "arrival_ns",
                 "last_emit_ns", "deadline", "_cancel", "_engine", "error",
                 "tag", "trace", "hold", "adapter", "denoise_steps",
                 "reveal_threshold")

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
        # block decoding (serving.block_decode): passes in which a block's
        # masked positions are revealed, and the confidence above which a
        # pass reveals more than its share (None: the static schedule)
        self.denoise_steps = None
        self.reveal_threshold = None

    @property
    def is_finished(self):
        return self.state == "finished"

    def cancel(self):
        """Request cancellation; the engine evicts the request (or drops
        it from the queue) on its next step.  Safe to call from any
        thread, any number of times, including after the request finished
        (the finish CAS in ``_finish`` makes the late cancel a
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


class _RequestLifecycle:
    """The half of the engine that knows requests and not the cache.

    ``add_request()`` enqueues (bounded queue, optional blocking
    backpressure); ``_sweep`` / ``_finish`` / ``_emit`` move a request
    through its states and hand its row back; ``generate()`` is the
    blocking convenience loop and ``drain()`` stops admission and
    finishes all outstanding work, both by calling ``step()``, which the
    engine (``serving.paged.LLMEngine``) defines together with admission
    and the device programs.
    """

    def __init__(self, max_slots, max_seq_len, queue_size, eos_token_id,
                 adapter_slots, tenant_buckets):
        if type(self) is _RequestLifecycle:
            raise TypeError("the request lifecycle alone has no step(); "
                            "construct serving.LLMEngine")
        self.max_slots = B = int(max_slots)
        self.max_seq_len = int(max_seq_len)
        self.queue_size = int(queue_size)
        self.eos_token_id = eos_token_id  # default for requests
        # tenant_buckets bounds the per-tenant telemetry cardinality:
        # TTFT/ITL histograms are keyed by a stable hash bucket, never by
        # raw tenant id (adapter_slots=0: no tenants, no such histograms)
        self.adapter_slots = int(adapter_slots or 0)
        self.tenant_buckets = int(tenant_buckets)

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

    def histogram_snapshot(self):
        """Copies of the per-engine histograms (point-in-time, safe to
        ``Histogram.merge`` across replicas — the fleet Router does)."""
        return {n: h.copy() for n, h in self.hists.items()}

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
        replica.  ``adapter`` names
        the tenant whose registered LoRA factors decorate this request's
        matmuls (None = base model); requires an engine built with
        ``adapter_slots > 0``."""
        if self._closed:
            raise EngineClosed("engine is drained; no new requests")
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

    def _emit(self, req, tok, events, **extra):
        """Record one generated token; finish on EOS / length (``extra``
        joins the token's event: a block-decoding engine's
        ``reveal_step``).  The event
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
                       "index": len(req.tokens) - 1, **extra})
        if req.eos_token_id is not None and int(tok) == req.eos_token_id:
            self._finish(req, "eos", events)
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish(req, "length", events)

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
                "active": sum(r is not None for r in self._slots),
                "queued": len(self._queue),
                "free_slots": len(self._free),
                "max_slots": self.max_slots,
                "closed": self._closed,
                "outstanding_tokens": self._outstanding,
                "decode_tps_ema": self._tps_ema,
            }
