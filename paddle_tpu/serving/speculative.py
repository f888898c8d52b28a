"""Speculative decoding over the shared paged KV arena
(``LLMEngine(model, draft_model=...)``).

Per-token decode latency is one full target-model dispatch per output
token.  Speculative decoding (Leviathan et al., "Fast Inference from
Transformers via Speculative Decoding", ICML 2023) breaks that coupling:
a small DRAFT model autoregressively proposes K tokens, then the target
model scores the whole block — committed token + K proposals — in ONE
fixed-shape verify program (``GPT.verify_paged``: positions ``[B, K+1]``
ride as operands, the same one-program / zero-steady-retrace economics
as ``decode_paged``).  An accepted prefix of the draft plus one
correction/bonus token is emitted, so a scheduler round yields between 1
and K+1 tokens per slot for K+2 cheap-draft dispatches and one target
dispatch.

Correctness contract:

* **Greedy** (``do_sample=False``) — a proposal is accepted while it
  equals the target's argmax at the preceding position; the first
  mismatch emits the target argmax instead.  The emitted stream is the
  target's own greedy chain, token-identical to the non-speculative
  paged engine (and to ``GPT.generate``) for ANY draft model — the draft
  only moves throughput, never output.
* **Sampling** — modified rejection sampling: proposal ``x ~ q`` is
  accepted with probability ``min(1, p(x)/q(x))``; on rejection the
  correction token is drawn from the residual ``norm(max(0, p - q))``
  (``serving.sampling.residual_sample``), and when every considered
  proposal is accepted a bonus token is drawn from ``p`` at the next
  position.  The marginal output distribution is exactly ``p`` — the
  same distribution the non-speculative engine samples — whatever the
  draft proposes.  (The PRNG *stream* differs from the non-speculative
  engine's — speculation consumes draws per round, not per token — so
  the guarantee is distributional, not bitwise; greedy stays bitwise.)

Memory model (PagedAttention, Kwon et al., SOSP 2023): both models' KV
blocks live in the ONE ``BlockPool`` — block ids form per-model
namespaces (the same id indexes either the target arena ``[L, n_blocks,
bs, nh, hd]`` or the draft arena ``[L_d, n_blocks, bs, nh_d, hd_d]``
depending on whose table holds it; draft blocks are never donated to the
target-namespace prefix tree).  The target's worst-case table is pinned
at admission exactly as in ``LLMEngine`` (``n_valid`` caps verify
writes to the reservation), while the draft table grows ahead of each
round and is ROLLED BACK after rejection by truncating the block table
and releasing refcounts — stale rejected-draft KV is simply overwritten
by later scatters (the causal mask ``kpos <= pos`` keeps it invisible
until then), so rollback never copies device memory.

Program economics: steady state is exactly ONE draft-step program and
ONE verify program (plus the bucketed prefill chunks), cached in the
per-model ``_model_programs`` registry — draft programs key under the
draft model instance, verify under the target, so a fleet of replicas
over the same pair shares both executables.  The fleet threads
``draft_model=`` through replicas, and the acceptance-rate EMA exported
from ``stats()`` feeds the Router's SLO math (see ``serving.router``).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import paged_attention as _pa
from ..profiler import counters
from ..profiler import devicetime as _devicetime
from ..profiler import flight
from ..profiler import trace as rtrace
from ..profiler.host_tracer import span
from .engine import (BlockDecodeUnsupported, LatentCacheUnsupported,
                     RecurrentStateUnsupported, WindowCacheUnsupported,
                     bucket_length)
from .kvcache import blocks_for_tokens
from .paged import LLMEngine, _model_programs
from .sampling import filter_logits, next_tokens, residual_sample

__all__ = ["SpeculativeLLMEngine"]


def _acceptance(logits, toks, q, nv, keys_data, do_sample, temp, top_k,
                top_p):
    """Distribution-preserving acceptance over one verified draft block
    (traced inside the verify program).

    ``logits[B, K1, V]`` are the target's scores at every drafted
    position, ``toks[B, K1]`` the committed token + K proposals,
    ``q[B, K, V]`` the draft's (filtered) proposal distributions,
    ``nv[B]`` the per-row valid-position count.  Returns
    ``(emit[B, K1], n_emit[B], new_keys_data)`` where ``emit[b, :n_emit]``
    is the row's accepted prefix plus its correction/bonus token.

    Sampled rows follow Leviathan et al. (ICML 2023): accept proposal
    ``x`` with probability ``min(1, p(x)/q(x))`` (as ``u*q(x) < p(x)``,
    which also accepts ``q(x)=0`` proposals outright), reject into a
    ``residual_sample`` draw, bonus-sample from ``p`` after a clean
    sweep.  Only an actual failed acceptance test counts as rejection —
    running out of draft budget (``nv < K+1``) is not one, so truncated
    rows still draw their final token from ``p``, keeping the marginal
    exactly the target distribution at every emitted position.  Greedy
    rows accept while the proposal equals the target
    argmax and emit the argmax at the first mismatch — the target's own
    greedy chain, bitwise."""
    B, K1, V = logits.shape
    K = K1 - 1
    rows = jnp.arange(B)
    keys = jax.random.wrap_key_data(keys_data)

    def srow(kk):
        ks = jax.random.split(kk, 4)
        return ks[0], ks[1], ks[2], ks[3]

    new_keys, k_acc, k_res, k_bonus = jax.vmap(srow)(keys)
    u = jax.vmap(lambda kk: jax.random.uniform(kk, (K,)))(k_acc)
    # the target distribution the non-speculative engine would sample
    # from: per-row filtered softmax at every position
    p = jax.vmap(lambda lg, t, tk, tp: jax.nn.softmax(
        filter_logits(lg, t, tk, tp), axis=-1))(logits, temp, top_k, top_p)
    greedy = jnp.argmax(logits, axis=-1)                      # [B, K1]
    acc = jnp.zeros(B, jnp.int32)
    rej = jnp.zeros(B, bool)
    for j in range(K):
        tokj = toks[:, j + 1]
        ptok = p[:, j][rows, tokj]
        qtok = q[:, j][rows, tokj]
        ok_s = u[:, j] * qtok < ptok
        ok_g = tokj == greedy[:, j]
        # a proposal is CONSIDERED only inside the row's draft budget and
        # before its first rejection — budget exhaustion is not a
        # rejection, so a truncated round (nv < K+1: final-token and
        # draft-starved rows) must still bonus-sample from p, never from
        # the residual
        considered = ~rej & (j < nv - 1)
        ok = jnp.where(do_sample, ok_s, ok_g)
        rej = rej | (considered & ~ok)
        acc = acc + (considered & ok).astype(jnp.int32)
    pin = p[rows, acc]                                        # [B, V]
    qin = q[rows, jnp.minimum(acc, K - 1)]
    t_res = jax.vmap(residual_sample)(pin, qin, k_res)
    t_bonus = jax.vmap(lambda kk, pr: jax.random.categorical(
        kk, jnp.log(jnp.maximum(pr, 1e-30))))(k_bonus, pin)
    t_fin = jnp.where(do_sample,
                      jnp.where(rej, t_res, t_bonus),
                      greedy[rows, acc]).astype(jnp.int32)
    tpad = jnp.concatenate([toks[:, 1:], jnp.zeros((B, 1), toks.dtype)],
                           axis=1)
    idx = jnp.arange(K1)[None, :]
    emit = jnp.where(idx < acc[:, None], tpad,
                     jnp.where(idx == acc[:, None], t_fin[:, None],
                               0)).astype(jnp.int32)
    return emit, acc + 1, jax.random.key_data(new_keys)


class SpeculativeLLMEngine(LLMEngine):
    """``LLMEngine`` with draft/verify speculative decoding.

    Extra knobs:

    * ``draft_model`` — the proposal ``GPTForCausalLM`` (same vocab as
      the target; layers/width/heads are free).  Required.
    * ``spec_k`` — proposals drafted per scheduler round (default 4);
      a round emits 1..K+1 tokens per running slot.
    """

    def __init__(self, model, *args, **kw):
        draft = kw.pop("draft_model", None)
        if draft is None:
            raise ValueError("SpeculativeLLMEngine requires draft_model=")
        k = int(kw.pop("spec_k", 4))
        if k < 1:
            raise ValueError(f"spec_k must be >= 1, got {k}")
        if draft.config.vocab_size != model.config.vocab_size:
            raise ValueError(
                f"draft vocab ({draft.config.vocab_size}) != target vocab "
                f"({model.config.vocab_size}); speculative acceptance "
                "compares the two distributions token for token")
        if any(m.cache_spec()["slot_state"] for m in (model, draft)):
            raise RecurrentStateUnsupported(
                "draft_model= with a target or a draft that has recurrent "
                "layers: verification rolls K/V back by position, and a "
                "recurrent state has none")
        if draft.cache_spec().get("decode_block"):
            raise BlockDecodeUnsupported(
                "draft_model= with a draft that decodes by blocks: a "
                "proposal is one token a row and launch")
        if any(m.cache_spec().get("window") for m in (model, draft)):
            raise WindowCacheUnsupported(
                "draft_model= with a target or a draft that has window "
                "layers: verification rolls K/V back by position in one "
                "pool, and their rows live in a ring of a second")
        if any(m.cache_spec().get("kv_row") for m in (model, draft)):
            raise LatentCacheUnsupported(
                "draft_model= with a target or a draft that caches latent "
                "rows: the verify and roll-back programs address K/V by "
                "head")
        self.draft_model = draft
        self.spec_k = k
        super().__init__(model, *args, **kw)

    # -- construction --------------------------------------------------------
    def _init_kv(self, c, B, S, nh, hd, dt):
        dc = self.draft_model.config
        if not dc.use_rope and S > dc.max_seq_len:
            raise ValueError(
                f"max_seq_len {S} exceeds the draft model's "
                f"learned-position table ({dc.max_seq_len})")
        super()._init_kv(c, B, S, nh, hd, dt)
        bs = self.pool.block_size
        dnh = dc.num_heads
        dhd = dc.hidden_size // dnh
        adt = (_pa.KV_DTYPES[self.kv_dtype] if self.kv_dtype
               else jnp.dtype(dc.dtype))
        from .arena import KV_POOL_SPEC
        if self.weight_dtype == "int8":
            from ..quantization import ptq_int8_decode_state
            self._dw = self.arena.declare_tree(
                "draft_weights", ptq_int8_decode_state(self.draft_model))
        else:
            self._dw = self.arena.declare_tree(
                "draft_weights", self.draft_model.decode_state())
        # the draft's arena shares the pool's BLOCK IDS, not its storage:
        # same n_blocks/block_size geometry, the draft model's own
        # layer/head shape
        self.arena.declare(
            "draft_pool_k",
            jnp.zeros((dc.num_layers, self.n_blocks, bs, dnh, dhd), adt),
            spec=KV_POOL_SPEC)
        self.arena.declare(
            "draft_pool_v",
            jnp.zeros((dc.num_layers, self.n_blocks, bs, dnh, dhd), adt),
            spec=KV_POOL_SPEC)
        if self.kv_dtype:
            self.arena.declare(
                "draft_scale_k",
                jnp.zeros((dc.num_layers, self.n_blocks, bs), jnp.float32))
            self.arena.declare(
                "draft_scale_v",
                jnp.zeros((dc.num_layers, self.n_blocks, bs), jnp.float32))
        else:
            self.arena.declare("draft_scale_k", None)
            self.arena.declare("draft_scale_v", None)
        key_size = jax.random.key_data(jax.random.key(0)).shape[0]
        self._dkeys = np.zeros((B, key_size), np.uint32)
        self._dbt = np.zeros((B, self.max_blocks), np.int32)
        self._dslot_blocks = [None] * B
        self._dchunk_jits = {}
        self._pdraft_jit = None
        self._pverify_jit = None
        # acceptance / per-round yield EMAs (the router's SLO math
        # re-anchors throughput on these; see Router.pick)
        self._acc_ema = -1.0          # < 0: no drafted round yet
        self._yield_ema = 0.0
        self._spec_drafted = 0
        self._spec_accepted = 0

    # draft pools live in the StateArena like the target's (same rebind
    # discipline through the donated draft programs)
    @property
    def _dk(self):
        return self.arena.get("draft_pool_k")

    @_dk.setter
    def _dk(self, v):
        self.arena.bind("draft_pool_k", v)

    @property
    def _dv(self):
        return self.arena.get("draft_pool_v")

    @_dv.setter
    def _dv(self, v):
        self.arena.bind("draft_pool_v", v)

    @property
    def _dsk(self):
        return self.arena.get("draft_scale_k")

    @_dsk.setter
    def _dsk(self, v):
        self.arena.bind("draft_scale_k", v)

    @property
    def _dsv(self):
        return self.arena.get("draft_scale_v")

    @_dsv.setter
    def _dsv(self, v):
        self.arena.bind("draft_scale_v", v)

    def release_kv(self):
        super().release_kv()
        self._dk = self._dv = self._dsk = self._dsv = None

    # -- compiled programs ---------------------------------------------------
    def _dchunk_for(self, bucket):
        """Draft-arena chunked prefill: the draft writes the prompt's KV
        into its own namespace (no prefix reuse — the tree's blocks hold
        target KV); the chunk's logits are dead and DCE'd."""
        fn = self._dchunk_jits.get(bucket)
        if fn is None:
            draft = self.draft_model

            def build():
                if self.kv_dtype:
                    def dchunk(dw, ids, start, length, bt, dk, dv, dsk,
                               dsv):
                        counters.inc("serving.retraces")  # trace-time only
                        dk, dv, dsk, dsv, _ = draft.prefill_paged(
                            dw, ids, start, length, bt, dk, dv, dsk, dsv)
                        return dk, dv, dsk, dsv
                    return jax.jit(dchunk, donate_argnums=(5, 6, 7, 8))

                def dchunk(dw, ids, start, length, bt, dk, dv):
                    counters.inc("serving.retraces")  # trace-time only
                    dk, dv, _ = draft.prefill_paged(
                        dw, ids, start, length, bt, dk, dv)
                    return dk, dv
                return jax.jit(dchunk, donate_argnums=(5, 6))
            fn = self.arena.program(
                _model_programs(draft),
                self._prog_key("serving.draft_prefill_paged"), build)
            self._dchunk_jits[bucket] = fn
        return fn

    def _pdraft(self):
        """ONE draft-step program: draft ``decode_paged`` + the proposal
        draw, returning the proposal AND the filtered distribution it was
        drawn from (``q`` — what the acceptance test divides by)."""
        if self._pdraft_jit is None:
            draft = self.draft_model
            # the draft's own head shape decides; its pools take no
            # shard_map route, so on a mesh it keeps the twin GSPMD
            # partitions
            dc = draft.config
            mode = ("off" if self.arena.multi_device else _pa.kernel_mode(
                dc.num_heads, dc.hidden_size // dc.num_heads))

            def build():
                if self.kv_dtype:
                    def dstep(dw, dk, dv, dsk, dsv, bt, tok, pos,
                              keys_data, do_sample, temp, top_k, top_p):
                        counters.inc("serving.retraces")
                        logits, dk, dv, dsk, dsv = draft.decode_paged(
                            dw, tok, pos, bt, dk, dv, dsk, dsv,
                            kernel=mode)
                        nxt, qdist, new_keys = next_tokens(
                            logits, keys_data, do_sample, temp, top_k,
                            top_p, with_dist=True)
                        return nxt, qdist, dk, dv, dsk, dsv, new_keys
                    return jax.jit(dstep, donate_argnums=(1, 2, 3, 4))

                def dstep(dw, dk, dv, bt, tok, pos, keys_data,
                          do_sample, temp, top_k, top_p):
                    counters.inc("serving.retraces")
                    logits, dk, dv = draft.decode_paged(
                        dw, tok, pos, bt, dk, dv, kernel=mode)
                    nxt, qdist, new_keys = next_tokens(
                        logits, keys_data, do_sample, temp, top_k,
                        top_p, with_dist=True)
                    return nxt, qdist, dk, dv, new_keys
                return jax.jit(dstep, donate_argnums=(1, 2))
            self._pdraft_jit = self.arena.program(
                _model_programs(draft),
                self._prog_key("serving.draft_paged"), build)
        return self._pdraft_jit

    def _pverify(self):
        """ONE verify program: ``verify_paged`` over the [B, K+1] block
        + the acceptance rule, returning only small int outputs (the host
        never pulls a logits tensor).  The K+1 token columns and K draft
        distributions ride as separate operands and are stacked inside
        the program, so the draft loop's outputs feed straight through
        device-to-device."""
        if self._pverify_jit is None:
            model = self.model
            K1 = self.spec_k + 1

            def build():
                # draft proposes on the BASE model; verify scores under
                # the target tenant's adapter, so Leviathan acceptance
                # stays distribution-preserving per row — the adapter
                # slab pytree + per-row ids lead the varargs when enabled
                lora = self.adapters is not None

                if self.kv_dtype:
                    def verify(w, pk, pv, sk, sv, bt, pos0, nv, keys_data,
                               do_sample, temp, top_k, top_p, *tq):
                        counters.inc("serving.retraces")
                        if lora:
                            aw, aid, *tq = tq
                        else:
                            aw = aid = None
                        toks = jnp.stack(tq[:K1], axis=1)
                        q = jnp.stack(tq[K1:], axis=1)
                        logits, pk, pv, sk, sv = model.verify_paged(
                            w, toks, pos0, nv, bt, pk, pv, sk, sv,
                            adapters=aw, adapter_ids=aid)
                        emit, n_emit, new_keys = _acceptance(
                            logits, toks, q, nv, keys_data, do_sample,
                            temp, top_k, top_p)
                        return emit, n_emit, pk, pv, sk, sv, new_keys
                    return jax.jit(verify, donate_argnums=(1, 2, 3, 4))

                def verify(w, pk, pv, bt, pos0, nv, keys_data,
                           do_sample, temp, top_k, top_p, *tq):
                    counters.inc("serving.retraces")
                    if lora:
                        aw, aid, *tq = tq
                    else:
                        aw = aid = None
                    toks = jnp.stack(tq[:K1], axis=1)
                    q = jnp.stack(tq[K1:], axis=1)
                    logits, pk, pv = model.verify_paged(
                        w, toks, pos0, nv, bt, pk, pv,
                        adapters=aw, adapter_ids=aid)
                    emit, n_emit, new_keys = _acceptance(
                        logits, toks, q, nv, keys_data, do_sample,
                        temp, top_k, top_p)
                    return emit, n_emit, pk, pv, new_keys
                return jax.jit(verify, donate_argnums=(1, 2))
            self._pverify_jit = self.arena.program(
                _model_programs(model),
                self._prog_key(f"serving.verify_paged[k{self.spec_k}]"),
                build)
        return self._pverify_jit

    # -- request intake ------------------------------------------------------
    def add_request(self, prompt, max_new_tokens=32, **kw):
        ids = np.asarray(
            prompt._data if hasattr(prompt, "_data") else prompt,
            dtype=np.int32).reshape(-1)
        need = blocks_for_tokens(
            max(1, int(ids.shape[0]) + int(max_new_tokens) - 1),
            self.pool.block_size)
        if 2 * need > self.pool.capacity:
            raise ValueError(
                f"request needs {need} KV blocks in EACH of the target "
                f"and draft namespaces but the shared pool only has "
                f"{self.pool.capacity} (n_blocks={self.n_blocks}, "
                f"block_size={self.pool.block_size})")
        return super().add_request(ids, max_new_tokens=max_new_tokens,
                                   **kw)

    def _reserve(self, req, events):
        """Reserve the draft namespace's prompt blocks alongside the
        target's all-or-nothing reservation: either BOTH models' tables
        are covered or nothing is allocated (the draft's decode-ahead
        blocks grow per round — see ``_grow_draft_tables``)."""
        T = int(req.prompt.shape[0])
        dneed = blocks_for_tokens(max(1, T), self.pool.block_size)
        with self._cond:
            short = dneed - self.pool.free_blocks
            if short > 0 and self.prefix is not None:
                self.kv_blocks_evicted += self.prefix.evict(short)
            if dneed > self.pool.free_blocks:
                self.kv_pool_exhausted_events += 1
                counters.inc("serving.kv.pool_exhausted")
                flight.record("serving.kv.pool_exhausted", rid=req.rid,
                              needed=dneed, free=self.pool.free_blocks,
                              injected=False)
                return False
            dblocks = self.pool.alloc_n(dneed)
        if not super()._reserve(req, events):
            with self._cond:
                for b in dblocks:
                    self.pool.release(b)
            return False
        with self._cond:
            s = req.slot
            self._dslot_blocks[s] = dblocks
            self._dbt[s] = 0
            self._dbt[s, :len(dblocks)] = dblocks
        return True

    # -- chunked prefill (both namespaces) -----------------------------------
    def _run_draft_chunk(self, slot, st):
        st["ddone"] = self._draft_prefill_tokens(
            slot, st["req"].prompt, st.get("ddone", 0))

    def _draft_prefill_tokens(self, slot, tokens, start):
        """One draft-arena prefill chunk over ``tokens[start:]``; returns
        the new prefilled count.  Shared by admission-time prefill (over
        the prompt) and migration adopt (over the full committed
        sequence — the draft namespace never migrates, it is throwaway
        proposal state, so the destination rebuilds it locally)."""
        T = int(len(tokens))
        remaining = T - start
        C = bucket_length(min(remaining, self.prefill_chunk),
                          self.min_bucket, self.prefill_chunk)
        take_n = min(remaining, C)
        ids = np.zeros((1, C), np.int32)
        ids[0, :take_n] = tokens[start:start + take_n]
        with span("serving.spec.draft_prefill"):
            df = self._dchunk_for(C)
            head = (self._dw, self.arena.operand(ids), np.int32(start),
                    np.int32(take_n), self.arena.operand(self._dbt[slot]))
            if self.kv_dtype:
                dargs = (*head, self._dk, self._dv, self._dsk, self._dsv)
                dn = (5, 6, 7, 8)
            else:
                dargs = (*head, self._dk, self._dv)
                dn = (5, 6)
            # program name == the _model_programs cache key (+ chunk
            # bucket), so devicetime/telemetry rows join the executable
            # that actually ran
            dname = (f"{self._prog_key('serving.draft_prefill_paged')}"
                     f"[c{C}]")
            self._maybe_capture(dname, df, *dargs)
            self._maybe_audit(dname, df, *dargs, donate_argnums=dn)
            _dt = _devicetime.note(dname)
            if self.kv_dtype:
                self._dk, self._dv, self._dsk, self._dsv = df(*dargs)
            else:
                self._dk, self._dv = df(*dargs)
            _devicetime.observe(_dt, self._dk)
        counters.inc("serving.spec.draft_prefill_chunks")
        return start + take_n

    def _run_chunk(self, slot, st, events):
        req = st["req"]
        T = int(req.prompt.shape[0])
        start = st["done"]
        C = bucket_length(min(T - start, self.prefill_chunk),
                          self.min_bucket, self.prefill_chunk)
        target_next = start + min(T - start, C)
        # the draft namespace gets no prefix-cache head start, so it may
        # owe several chunks on a prefix hit: keep it level with where
        # the target lands this pass, so both finish together
        while st.setdefault("ddone", 0) < target_next:
            self._run_draft_chunk(slot, st)
        super()._run_chunk(slot, st, events)
        if slot not in self._prefill_state:
            # prefill completed: seed the draft-side PRNG chain —
            # independent of the verify stream by construction (any
            # deterministic per-request seed works; acceptance corrects
            # whatever the draft proposes)
            self._dkeys[slot] = np.asarray(jax.random.key_data(
                jax.random.fold_in(jax.random.key(req.seed), 0x5BEC)))

    # -- KV migration --------------------------------------------------------
    def _adopt_extra(self, slot, req, mig):
        """Rebuild the draft-side state for a migrated request.  The
        draft namespace's KV is throwaway proposal state and never rides
        a migration: the destination re-prefills the committed sequence
        into its own draft arena here (bounded: ceil(pos/chunk) draft
        dispatches).  A pool that cannot cover the draft table leaves
        the row draft-starved — ``_grow_draft_tables`` downgrades it to
        plain decode (``serving.spec.draft_starved``), so migration onto
        a tight decode replica degrades throughput, never correctness.
        Caller holds ``_cond``."""
        pos = int(mig["pos"])
        dneed = blocks_for_tokens(max(pos, 1), self.pool.block_size)
        short = dneed - self.pool.free_blocks
        if short > 0 and self.prefix is not None:
            self.kv_blocks_evicted += self.prefix.evict(short)
        if dneed > self.pool.free_blocks:
            self._dslot_blocks[slot] = None
            self._dbt[slot] = 0
            counters.inc("serving.spec.draft_starved")
            return
        dblocks = self.pool.alloc_n(dneed)
        self._dslot_blocks[slot] = dblocks
        self._dbt[slot] = 0
        self._dbt[slot, :len(dblocks)] = dblocks
        seq = np.concatenate(
            [mig["prompt"], np.asarray(mig["tokens"], np.int32)])[:pos]
        done = 0
        while done < pos:
            done = self._draft_prefill_tokens(slot, seq, done)
        self._dkeys[slot] = np.asarray(jax.random.key_data(
            jax.random.fold_in(jax.random.key(req.seed), 0x5BEC)))

    # -- the draft/verify round ----------------------------------------------
    def _grow_draft_tables(self, nv):
        """Extend each running row's draft table to cover this round's
        draft writes (positions ``pos .. pos + nv - 1``).  A row the pool
        cannot cover is downgraded to ``nv=1`` with drafting skipped
        (``serving.spec.draft_starved``) — the verify program still emits
        its one plain-decode token, so starvation degrades throughput,
        never correctness.  Returns the per-row draft-ready mask."""
        bs = self.pool.block_size
        dready = np.zeros(self.max_slots, np.bool_)
        with self._cond:
            for s in range(self.max_slots):
                if not self._running[s]:
                    continue
                if self._dslot_blocks[s] is None:
                    # no draft table at all: its proposals would have
                    # been drafted against the trash block — degrade to
                    # plain decode like the pool-exhausted path
                    nv[s] = 1
                    counters.inc("serving.spec.draft_starved")
                    continue
                tbl = self._dslot_blocks[s]
                need = blocks_for_tokens(int(self._pos[s]) + int(nv[s]),
                                         bs)
                grow = need - len(tbl)
                if grow > 0:
                    short = grow - self.pool.free_blocks
                    if short > 0 and self.prefix is not None:
                        self.kv_blocks_evicted += self.prefix.evict(short)
                    if grow > self.pool.free_blocks:
                        nv[s] = 1
                        counters.inc("serving.spec.draft_starved")
                        continue
                    fresh = self.pool.alloc_n(grow)
                    self._dbt[s, len(tbl):need] = fresh
                    tbl.extend(fresh)
                dready[s] = True
        return dready

    def _rollback_draft(self, s):
        """Truncate the row's draft table to its committed length and
        release the blocks that held only rejected proposals — the
        block-table twin of vLLM's free-on-preempt, with no device
        copies: stale in-block KV is overwritten by the next round's
        scatter and masked until then."""
        tbl = self._dslot_blocks[s]
        if tbl is None:
            return
        keep = blocks_for_tokens(max(int(self._pos[s]), 1),
                                 self.pool.block_size)
        if len(tbl) <= keep:
            return
        with self._cond:
            drop = tbl[keep:]
            del tbl[keep:]
            self._dbt[s, keep:] = 0
            for b in drop:
                self.pool.release(b)
        counters.inc("serving.spec.rollback_blocks", len(drop))

    def _spec_note_round(self, drafted, accepted, emitted, n_active):
        with self._cond:
            self._spec_drafted += drafted
            self._spec_accepted += accepted
            if drafted > 0:
                rate = accepted / drafted
                self._acc_ema = (rate if self._acc_ema < 0 else
                                 self._ema_alpha * rate
                                 + (1 - self._ema_alpha) * self._acc_ema)
            y = emitted / max(n_active, 1)
            self._yield_ema = (y if self._yield_ema <= 0 else
                               self._ema_alpha * y
                               + (1 - self._ema_alpha) * self._yield_ema)
            acc_g, yld_g = max(self._acc_ema, 0.0), self._yield_ema
        counters.set_gauge("serving.spec.acceptance", acc_g)
        counters.set_gauge("serving.spec.yield", yld_g)

    def _decode_step(self, events):
        """One speculative round for every running slot: K+1 draft-step
        dispatches (K proposals + one coverage step that writes the last
        proposal's draft KV, so the draft namespace never develops holes
        after a clean sweep), then ONE verify dispatch, then host-side
        bookkeeping — emit the accepted block, advance positions by the
        per-row yield, roll the draft tables back past rejections."""
        active = [(s, r) for s, r in enumerate(self._slots)
                  if r is not None and r.state == "running"]
        if not active:
            return
        self._observe("serving.decode_occupancy",
                      len(active) / self.max_slots)
        K = self.spec_k
        K1 = K + 1
        nv = np.ones(self.max_slots, np.int32)
        for s, r in active:
            # emit at most the row's remaining token budget this round —
            # caps verify writes inside the admission reservation
            nv[s] = min(K1, max(r.max_new_tokens - len(r.tokens), 1))
        pos0 = np.where(self._running, self._pos, 0).astype(np.int32)
        t0 = time.perf_counter()
        dready = self._grow_draft_tables(nv)
        tr_on = rtrace.enabled()
        t0_tr = time.perf_counter_ns() if tr_on else 0
        if self._drained_ns:
            # the round's launches carry no gap_ns; they take the stamp a
            # prefill read-back left, so that no later launch claims it
            self._drained_ns = 0
        with span("serving.spec.round"):
            df = self._pdraft()
            op = self.arena.operand
            cur = op(self._tok)
            dkeys = op(self._dkeys)
            ds_eff = self._dosample & self._running
            dosample = op(ds_eff)
            temp = op(self._temp)
            topk = op(self._topk)
            topp = op(self._topp)
            ts, qs = [cur], []
            for j in range(K1):
                part = self._running & dready & (nv > j)
                bt_eff = np.where(part[:, None], self._dbt,
                                  0).astype(np.int32)
                pos_j = np.where(part, pos0 + j, 0).astype(np.int32)
                head = ((self._dw, self._dk, self._dv, self._dsk,
                         self._dsv) if self.kv_dtype
                        else (self._dw, self._dk, self._dv))
                dn = (1, 2, 3, 4) if self.kv_dtype else (1, 2)
                dargs = (*head, op(bt_eff), cur,
                         op(pos_j), dkeys, dosample, temp, topk,
                         topp)
                dname = self._prog_key("serving.draft_paged")
                if j == 0:
                    self._maybe_capture(dname, df, *dargs)
                    self._maybe_audit(dname, df, *dargs,
                                      donate_argnums=dn)
                _dt = _devicetime.note(dname)
                out = df(*dargs)
                _devicetime.observe(_dt, out)
                if self.kv_dtype:
                    (cur, qrow, self._dk, self._dv, self._dsk, self._dsv,
                     dkeys) = out
                else:
                    cur, qrow, self._dk, self._dv, dkeys = out
                if j < K:
                    ts.append(cur)
                    qs.append(qrow)
            counters.inc("serving.spec.draft_steps", K1)
            vf = self._pverify()
            bt_eff = np.where(self._running[:, None], self._bt,
                              0).astype(np.int32)
            vhead = ((self._w, self._pk, self._pv, self._sk, self._sv)
                     if self.kv_dtype else (self._w, self._pk, self._pv))
            vdn = (1, 2, 3, 4) if self.kv_dtype else (1, 2)
            if self.adapters is not None:
                aid_eff = np.where(self._running, self._aid,
                                   0).astype(np.int32)
                aext = (self.adapters.slabs(), op(aid_eff))
            else:
                aext = ()
            vargs = (*vhead, op(bt_eff), op(pos0),
                     op(nv), op(self._keys), dosample,
                     temp, topk, topp, *aext, *ts, *qs)
            vname = self._prog_key(f"serving.verify_paged[k{self.spec_k}]")
            self._maybe_capture(vname, vf, *vargs)
            self._maybe_audit(vname, vf, *vargs, donate_argnums=vdn)
            _dt = _devicetime.note(vname)
            out = vf(*vargs)
            _devicetime.observe(_dt, out)
            if self.kv_dtype:
                (emit, n_emit, self._pk, self._pv, self._sk, self._sv,
                 new_keys) = out
            else:
                emit, n_emit, self._pk, self._pv, new_keys = out
            emit = np.asarray(emit)
            n_emit = np.asarray(n_emit)
        if tr_on:
            t1_tr = time.perf_counter_ns()
            for _s, r in active:
                if r.trace is not None:
                    r.trace.add_span("decode.iter", t0_tr, t1_tr,
                                     batch=len(active))
        self._keys = np.array(new_keys)           # mutable host copies
        self._dkeys = np.array(np.asarray(dkeys))
        counters.inc("serving.spec.verify_steps")
        counters.inc("serving.decode_steps")
        counters.inc("serving.decode.sampled_steps", int(ds_eff.any()))
        # a round uploads its rows' state from the host mirrors, every time
        counters.inc("serving.decode.upload_steps")
        # and reads its tokens back before the next round
        counters.inc("serving.decode.overlapped_steps", 0)
        emitted = int(sum(int(n_emit[s]) for s, _ in active))
        self._note_decode(emitted, time.perf_counter() - t0)
        counters.inc("serving.decode_tokens", emitted)
        if self.kv_dtype:
            counters.inc("serving.kv.quant.decode_tokens", emitted)
        drafted = int(sum(int(nv[s]) - 1 for s, _ in active))
        accepted = int(sum(int(n_emit[s]) - 1 for s, _ in active))
        if drafted:
            counters.inc("serving.spec.drafted", drafted)
            counters.inc("serving.spec.accepted", accepted)
            counters.inc("serving.spec.rejected", drafted - accepted)
        self._spec_note_round(drafted, accepted, emitted, len(active))
        for s, req in active:
            n = int(n_emit[s])
            self._tok[s] = int(emit[s, n - 1])
            self._pos[s] += n
            self._rollback_draft(s)
            for i in range(n):
                if req.state != "running":   # EOS landed mid-block
                    break
                self._emit(req, int(emit[s, i]), events)

    # -- teardown / stats ----------------------------------------------------
    def _release_blocks(self, slot, req, reason):
        super()._release_blocks(slot, req, reason)
        dbl = self._dslot_blocks[slot]
        self._dslot_blocks[slot] = None
        self._dbt[slot] = 0
        if dbl:
            # never donated to the prefix tree: the tree's blocks are
            # target-namespace KV, a draft block would be garbage there
            for b in dbl:
                self.pool.release(b)

    def stats(self):
        with self._cond:
            st = super().stats()
            st.update({
                "speculative": True,
                "spec_k": self.spec_k,
                "spec_acceptance_ema": (None if self._acc_ema < 0
                                        else self._acc_ema),
                "spec_yield_ema": self._yield_ema,
                "spec_drafted": self._spec_drafted,
                "spec_accepted": self._spec_accepted,
                "draft_prefill_programs": len(self._dchunk_jits),
            })
        return st
