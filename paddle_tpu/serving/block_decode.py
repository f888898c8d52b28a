"""Serving a model that generates by diffusion over blocks
(``models/sdar.py``): ``LLMEngine`` with another decode launch.

``LLMEngine(model)`` returns this subclass for a model whose
``cache_spec()`` names a ``decode_block`` (``length`` positions a block,
the ``mask_token_id`` they start as, the default number of ``steps``).
Admission, block reservation, chunked prefill's scheduling, block tables,
``step()`` and the request lifecycle are the base engine's; what differs
is what a decode launch is.

A row holds a **block** of ``B`` positions: its tokens (mask tokens where
nothing is revealed yet), for each position the pass that revealed it
(``GIVEN`` for a prompt token, ``MASKED`` while it is masked), the passes
run so far, and the block's first position.  A launch is one pass of every
running row's block through the model (``decode_paged``: attention over
the row's committed prefix in the pool plus the block's own fresh K/V, all
``B`` positions seeing all ``B``).  Then, per row:

* a block with a mask left is **denoised**: at each masked position the
  candidate ``x0`` (arg-max when greedy, else the ``serving/sampling``
  draw) and its confidence ``c = softmax(logits)[x0]``; the pass reveals
  the ``n_s`` most confident masked positions, ``n_s = B // S`` plus 1 for
  the first ``B mod S`` passes of the request's ``S`` denoising steps (the
  *static* schedule), or, given a threshold ``tau``, every masked position
  with ``c > tau`` when there are at least ``n_s`` of them (*dynamic*).  A
  revealed token is never masked again.  The pass wrote nothing to the
  pool: the block's K/V change until its last token is revealed.
* a block with no mask left is in its **commit** pass: the same launch
  scattered its K/V into the row's own pool blocks, its tokens are handed
  back for emission, and the row moves on to the next block, all masks.

So a block costs between 2 and ``S + 1`` launches, a row yields no token
on most launches and up to ``B`` on the one that commits, and rows are out
of phase with each other.  The prompt's whole blocks are prefilled under
the block-causal mask (``prefill_paged``; no chunk samples anything); its
remainder of ``T mod B`` tokens opens the first generated block as given.

The rows' state lives on the device between launches as the base engine's
``tok`` / ``pos`` do: the decode program takes it, hands the next
launch's back, and the host uploads an array again only after
``_write_slot`` named it.  One array is read back a launch: per row the
block's tokens, their reveal passes, the passes run, and whether it
committed.

Records (registered by this engine alone): counters
``serving.diffusion.row_passes`` (running rows of each launch, denoise or
commit), ``serving.diffusion.commits``, ``serving.diffusion.revealed``;
histogram ``serving.diffusion.passes_per_block``;
``serving.decode_tokens`` counts tokens emitted.  A token's event carries
``reveal_step`` (``GIVEN`` never appears: prompt tokens are not emitted),
a commit adds a ``{"type": "block"}`` event (the whole block, also what
``max_new_tokens`` cut off) and, on a traced request, a ``decode.block``
span.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import block_attention as _ba
from ..profiler import counters
from ..profiler import metrics
from ..profiler.host_tracer import span
from .engine import BlockDecodeUnsupported, bucket_length
from .kvcache import blocks_for_tokens
from .paged import LLMEngine, _mask_idle
from .sampling import next_tokens

__all__ = ["BlockDecodeLLMEngine", "GIVEN", "MASKED", "decode_program"]

#: a position's reveal pass when it was given (a prompt token) / while it
#: is still masked
GIVEN = -1
MASKED = -2

# the decode program's per-slot operands, in the order it takes them
_OPERANDS = ("bt", "btok", "brstep", "bstep", "pos", "running", "keys",
             "dosample", "temp", "topk", "topp", "nsteps", "tau")


def _reveal(logits, tok, rstep, step, nsteps, tau, keys_data, do_sample,
            temp, top_k, top_p):
    """The denoising tail over ``logits [S, B, V]``: the blocks after this
    pass's reveal ``(tok, rstep [S, B])`` and the rows' new keys.  A row
    with no mask left comes back as it went in."""
    S, B, V = logits.shape
    rep = lambda x: jnp.repeat(x, B)                       # noqa: E731
    # one key a position, split off the row's key; the row keeps the first
    keys = jax.vmap(lambda k: jax.random.split(k, B + 1))(
        jax.random.wrap_key_data(keys_data))
    x0, _ = next_tokens(
        logits.reshape(S * B, V),
        jax.random.key_data(keys[:, 1:]).reshape(S * B, -1),
        rep(do_sample), rep(temp), rep(top_k), rep(top_p))
    x0 = x0.reshape(S, B)
    # the candidate's probability under the plain softmax: its logit less
    # the log of the sum, no sort
    conf = jnp.exp(jnp.take_along_axis(logits, x0[..., None], -1)[..., 0]
                   - jax.nn.logsumexp(logits, axis=-1))
    masked = rstep == MASKED
    n_s = B // nsteps + (step < B % nsteps)
    c = jnp.where(masked, conf, -1.0)
    idx = jnp.arange(B)
    # a position's rank among the masked ones, ties to the lower index
    ahead = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None]) & (idx[None, :] < idx[:, None]))
    static = masked & (ahead.sum(-1) < n_s[:, None])
    high = masked & (conf > tau[:, None])
    reveal = jnp.where((high.sum(-1) >= n_s)[:, None], high, static)
    return (jnp.where(reveal, x0, tok),
            jnp.where(reveal, step[:, None], rstep),
            jax.random.key_data(keys[:, 0]))


def decode_program(model, mode, B, mask_id):
    """The one decode program (``jit_decode``): a pass of every running
    row's block, the reveal, and the next launch's own operands.  Takes
    the weights, the pool and the ``step_state`` (both donated), then the
    per-slot operands in ``_OPERANDS``' order; returns ``(out, pool,
    state, tok, rstep, step, pos, keys)`` with ``out [slots, 2 B + 2]``
    the read-back: the block as this pass leaves it (a committed one as it
    was committed) and its reveal passes, the passes it has had, whether
    it committed."""
    def decode(w, pool, st, bt, tok, rstep, step, pos, running, keys_data,
               do_sample, temp, top_k, top_p, nsteps, tau):
        counters.inc("serving.retraces")      # trace-time only
        bt_e, pos_e, ds_e, _ = _mask_idle(running, bt, pos, do_sample)
        commit = running & (rstep != MASKED).all(-1)
        logits, pool, st = model.decode_paged(
            w, tok, pos_e, bt_e, pool, st, running, commit, kernel=mode)
        tok_r, rstep_r, new_keys = _reveal(
            logits, tok, rstep, step, nsteps, tau, keys_data, ds_e, temp,
            top_k, top_p)
        out = jnp.concatenate(
            [tok_r, rstep_r, (step + 1)[:, None],
             commit[:, None].astype(jnp.int32)], -1)
        # a committed row opens the next block, all masks; a row that is
        # not running keeps what it came in with
        go = (running & ~commit)[:, None]
        c2 = commit[:, None]
        return (out, pool, st,
                jnp.where(c2, mask_id, jnp.where(go, tok_r, tok)),
                jnp.where(c2, MASKED, jnp.where(go, rstep_r, rstep)),
                jnp.where(commit, 0, step + go[:, 0]),
                pos + B * commit,
                jnp.where(running[:, None], new_keys, keys_data))
    return decode


class BlockDecodeLLMEngine(LLMEngine):
    """``LLMEngine`` for a model that decodes by blocks.  Requests take
    two more knobs: ``denoise_steps`` (passes in which a block is
    revealed, 1 to the block length; the model's own by default) and
    ``reveal_threshold`` (``tau`` of the dynamic rule; ``None``: static).
    """

    def __init__(self, model, *args, **kw):
        spec = dict(model.cache_spec()["decode_block"])
        asked = {"draft_model=": kw.get("draft_model") is not None,
                 "kv_dtype=": kw.get("kv_dtype") is not None,
                 "host_kv_blocks=": int(kw.get("host_kv_blocks") or 0) > 0,
                 "adapter_slots=": int(kw.get("adapter_slots") or 0) > 0,
                 "mesh=": kw.get("mesh") is not None}
        if any(asked.values()):
            raise BlockDecodeUnsupported(
                f"{type(model).__name__} decodes by blocks of "
                f"{spec['length']} positions, which "
                + ", ".join(k for k, v in asked.items() if v)
                + " cannot carry yet")
        kw.pop("draft_model", None)
        self.block_length = B = int(spec["length"])
        self.mask_token_id = int(spec["mask_token_id"])
        self.default_steps = int(spec["steps"])
        super().__init__(model, *args, **kw)
        for what, n in (("block_size", self.block_size),
                        ("prefill_chunk", self.prefill_chunk),
                        ("min_bucket", self.min_bucket),
                        ("max_seq_len", self.max_seq_len)):
            if n % B:
                raise ValueError(
                    f"{what}={n} is not whole blocks of {B} positions: a "
                    "block would straddle two K/V blocks, or a chunk end "
                    "inside one")
        c = model.config
        self.kv_kernel = _ba.kernel_mode(c.num_heads, c.num_kv_heads,
                                         c.head_dim, B)
        slots = self.max_slots
        self._btok = np.full((slots, B), self.mask_token_id, np.int32)
        self._brstep = np.full((slots, B), MASKED, np.int32)
        self._bstep = np.zeros(slots, np.int32)
        self._nsteps = np.full(slots, self.default_steps, np.int32)
        self._tau = np.full(slots, np.inf, np.float32)
        self._operand_names = _OPERANDS
        self._stale = set(_OPERANDS)
        self.hists["serving.diffusion.passes_per_block"] = metrics.Histogram(
            "serving.diffusion.passes_per_block", "count")

    # -- requests ------------------------------------------------------------
    def add_request(self, prompt, max_new_tokens=32, denoise_steps=None,
                    reveal_threshold=None, **kw):
        if kw.get("hold_after_prefill"):
            raise BlockDecodeUnsupported(
                "hold_after_prefill parks a request for migration, which "
                "a block-decoding model's rows cannot take yet")
        steps = self.default_steps if denoise_steps is None \
            else int(denoise_steps)
        if not 1 <= steps <= self.block_length:
            raise ValueError(f"denoise_steps={steps} outside [1, "
                             f"{self.block_length}]")
        with self._cond:     # admission reads the knobs under this lock
            req = super().add_request(prompt, max_new_tokens=max_new_tokens,
                                      **kw)
            req.denoise_steps = steps
            req.reveal_threshold = (None if reveal_threshold is None
                                    else float(reveal_threshold))
        return req

    def _blocks_needed(self, T, max_new):
        """Every block is committed whole, the last one too."""
        B = self.block_length
        return blocks_for_tokens(-(-(T + max_new) // B) * B,
                                 self.pool.block_size)

    def _refuse_recurrent(self, what):
        raise BlockDecodeUnsupported(
            f"{what} hands over one token and one position a row; a "
            "block-decoding row holds a block in the middle of its passes")

    # -- compiled programs ---------------------------------------------------
    def _build_pchunk(self):
        model = self.model

        def pchunk(w, ids, start, length, bt, pool, st):
            counters.inc("serving.retraces")  # trace-time only
            return model.prefill_paged(w, ids, start, length, bt, pool, st)
        return jax.jit(pchunk, donate_argnums=(5, 6))

    def _build_pdecode(self):
        return jax.jit(decode_program(
            self.model, self.kv_kernel, self.block_length,
            self.mask_token_id), donate_argnums=(1, 2))

    # -- chunked prefill: whole blocks only ----------------------------------
    def _run_chunk(self, slot, st, events):
        req = st["req"]
        B = self.block_length
        T = int(req.prompt.shape[0])
        whole = T // B * B            # what is prefilled and cached
        start = st["done"]
        if start < whole:
            remaining = whole - start
            C = bucket_length(min(remaining, self.prefill_chunk),
                              self.min_bucket, self.prefill_chunk)
            take_n = min(remaining, C)
            with span("serving.prefill.operands"):
                ids = np.zeros((1, C), np.int32)
                ids[0, :take_n] = req.prompt[start:start + take_n]
                op = self.arena.operand
                if "bt" not in st:
                    # the slot's table was written whole at admission
                    st["bt"] = op(self._bt[slot].copy())
                self._observe("serving.prefill_occupancy", take_n / C)
                tr = req.trace
                t0_tr = time.perf_counter_ns() if tr is not None else 0
                pf = self._pchunk_for(C)
                pargs = (self._w, op(ids), np.int32(start),
                         np.int32(take_n), st["bt"], self._pk, self._st)
            with self._launch_span("serving.prefill.dispatch"):
                self._pk, self._st = self._dispatch(
                    f"serving.{self._prog_key('prefill_paged')}[c{C}]", pf,
                    pargs, (5, 6))
            if tr is not None:
                tr.add_span("prefill.chunk", t0_tr, time.perf_counter_ns(),
                            chunk=C, start=start, take=take_n)
            counters.inc("serving.kv.prefill_chunks")
            st["done"] = start = start + take_n
        if start < whole:
            return
        # the prompt's remainder opens the first generated block as given;
        # no chunk sampled anything: the first token comes with the first
        # commit, and _emit / TTFT follow that
        del self._prefill_state[slot]
        counters.inc("serving.prefill_batches")
        btok = np.full(B, self.mask_token_id, np.int32)
        rstep = np.full(B, MASKED, np.int32)
        btok[:T - whole] = req.prompt[whole:]
        rstep[:T - whole] = GIVEN
        tau = req.reveal_threshold
        with span("serving.prefill.wait") as w:
            # the row's key is made on the device behind the chunks: its
            # read-back is the prefill's, and waits for them
            key = np.asarray(jax.random.key_data(jax.random.key(req.seed)))
        self._drained(w)
        with span("serving.prefill.emit"):
            with self._cond:
                self._write_slot(
                    slot, btok=btok, brstep=rstep, bstep=0, pos=whole,
                    keys=key, temp=req.temperature, topk=req.top_k,
                    topp=req.top_p, dosample=req.do_sample, running=True,
                    nsteps=req.denoise_steps,
                    tau=np.inf if tau is None else tau)
            req.state = "running"

    # -- decode: one pass of every running row's block -----------------------
    def _decode_step(self, events):
        active = [(s, r) for s, r in enumerate(self._slots)
                  if r is not None and r.state == "running"]
        if not active:
            return
        B = self.block_length
        with span("serving.decode.operands"):
            # one clock pair a launch, as the base engine's
            t0 = time.perf_counter_ns()
            self._observe("serving.decode_occupancy",
                          len(active) / self.max_slots)
            dec = self._pdecode()
            tail, uploaded = self._decode_operands()
            sampled = bool((self._dosample & self._running).any())
            dargs = (self._w, self._pk, self._st, *tail)
        with self._launch_span("serving.decode.dispatch"):
            (out, self._pk, self._st, tok, rstep, step, pos,
             keys) = self._dispatch(
                f"serving.{self._prog_key('decode_paged')}", dec, dargs,
                (1, 2))
            with self._cond:
                # the program's own outputs are the next launch's operands
                self._dev.update(btok=tok, brstep=rstep, bstep=step,
                                 pos=pos, keys=keys)
                self._keys_host = None
        with span("serving.decode.wait") as w:    # the one read-back
            out = np.asarray(out)
        t1 = time.perf_counter_ns()
        self._drained(w, t1)
        emitted = revealed = commits = 0
        with span("serving.decode.emit"):
            for s, req in active:
                toks, rsteps = out[s, :B], out[s, B:2 * B]
                passes = int(out[s, 2 * B])
                if not out[s, 2 * B + 1]:
                    # a denoising pass: mirror what the program carried
                    revealed += int(((self._brstep[s] == MASKED)
                                     & (rsteps != MASKED)).sum())
                    self._btok[s], self._brstep[s] = toks, rsteps
                    self._bstep[s] = passes
                    continue
                commits += 1
                self._observe("serving.diffusion.passes_per_block", passes)
                first = int(self._pos[s])
                self._btok[s] = self.mask_token_id
                self._brstep[s] = MASKED
                self._bstep[s] = 0
                self._pos[s] = first + B
                if req.trace is not None:
                    req.trace.add_span("decode.block", t0, t1,
                                       passes=passes, batch=len(active))
                events.append({"type": "block", "request": req,
                               "start": first, "passes": passes,
                               "tokens": toks.tolist(),
                               "reveal_steps": rsteps.tolist()})
                for t, r in zip(toks, rsteps):
                    if r == GIVEN or req.is_finished:
                        continue
                    emitted += 1
                    self._emit(req, int(t), events, reveal_step=int(r))
            self._note_decode(emitted, (t1 - t0) * 1e-9)
            counters.inc("serving.decode_steps")
            counters.inc("serving.decode.sampled_steps", int(sampled))
            counters.inc("serving.decode.upload_steps", int(uploaded))
            # each launch is read back before the next is made
            counters.inc("serving.decode.overlapped_steps", 0)
            counters.inc("serving.decode_tokens", emitted)
            counters.inc("serving.diffusion.row_passes", len(active))
            counters.inc("serving.diffusion.commits", commits)
            counters.inc("serving.diffusion.revealed", revealed)
