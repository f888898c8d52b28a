"""SDAR (``model_type: sdar_moe``): a Qwen3-MoE decoder that generates by
diffusion over blocks of tokens.

The published configuration (arXiv:2510.06303; the Chat release's
``config.json``) is a pre-norm RMSNorm decoder: grouped-query attention
(``num_key_value_heads`` K/V heads under ``num_attention_heads`` query
heads, head ``h`` reads K/V head ``h // G``) with an RMSNorm over each
query's and key's head dims (one gain of ``head_dim`` each, shared by the
heads) and rotary positions over the whole head as two halves; every layer
a layer of ``num_experts`` experts, the top ``num_experts_per_tok`` of a
plain softmax renormalised to sum to 1, no shared expert; an untied head.

What sets it apart is the MASK and the way of generating.  With ``B`` the
block length and ``blk(i) = i // B`` over absolute positions, position
``i`` sees ``j`` iff ``blk(j) <= blk(i)``: causal over blocks, both ways
inside one.  A block is generated by denoising: it starts as ``B`` mask
tokens (after the prompt's remainder, if any), each pass runs the whole
block and reveals its most confident masked positions, and the pass after
the last reveal runs the final tokens once more and caches their K/V.  The
logit at a masked position predicts THAT position (no shift).  The
schedule (how many a pass reveals) lives in ``serving/block_decode.py``;
this file is the model's step.

:meth:`SdarMoeForCausalLM.cache_spec` tells the serving engine what it
caches: one row ``[k ; v]`` of ``2 * n_kv * head_dim`` values per token
and layer (``kv_row``, no head axis to pad: ``kernels/block_attention``),
two counters of the expert layers' load, and under ``decode_block`` that a
decode launch is one pass over a block of ``length`` positions a row.

The block is written once (:meth:`_layers`), as in ``deepseek_v2.py``: the
three entry points differ only in the function that reaches the cache.
Weights are stacked over the layers and indexed where they lie; the
experts go through ``kernels/moe.held_expert_ffn`` with all of them held.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.dispatch import matmul_precision
from ..core.tensor import Tensor
from ..kernels import block_attention as _ba
from ..kernels import moe as _moe
from ..kernels._shapes import NEG_INF
from ..kernels.rms_norm import rms_norm_reference as _rms
from ..nn.layer.layers import Layer
from ..profiler import host_tracer as _trace

#: cached positions a prefill chunk attends to per step of its walk over
#: the live rows (a whole number of blocks)
_KEY_TILE = 1024


class SdarConfig:
    """The sizes of an ``sdar_moe`` model under this package's names
    (``from_hf`` takes the published ``config.json`` keys).  What the
    config has no key for is the family's convention: ``block_length`` and
    ``denoising_steps`` 4 (the Chat release's defaults), ``mask_token_id``
    151669 (the tokenizer's ``<|MASK|>``)."""

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 moe_intermediate_size=768, num_layers=48, num_heads=32,
                 num_kv_heads=4, head_dim=128, num_experts=128,
                 num_experts_per_tok=8, rms_norm_eps=1e-6,
                 rope_theta=1000000.0, max_seq_len=32768, block_length=4,
                 denoising_steps=4, mask_token_id=151669,
                 initializer_range=0.02, dtype="float32"):
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_heads} query heads do not group over "
                             f"{num_kv_heads} K/V heads")
        if head_dim % 2:
            raise ValueError("head_dim must be even")
        if not 1 <= num_experts_per_tok <= num_experts:
            raise ValueError("num_experts_per_tok outside the experts")
        if not 1 <= denoising_steps <= block_length:
            raise ValueError("a block of B positions takes 1 to B "
                             "denoising steps")
        if not 0 <= mask_token_id < vocab_size:
            raise ValueError("mask_token_id outside the vocabulary")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.max_seq_len = max_seq_len
        self.block_length = int(block_length)
        self.denoising_steps = int(denoising_steps)
        self.mask_token_id = int(mask_token_id)
        self.initializer_range = initializer_range
        self.dtype = dtype
        # what the serving engine asks of any model's config
        self.use_rope = True

    @classmethod
    def from_hf(cls, hf, **kw):
        """From the keys of the published ``config.json``."""
        if (hf.get("decoder_sparse_step", 1) != 1 or hf.get("mlp_only_layers")
                or not hf.get("norm_topk_prob", True)
                or hf.get("attention_bias") or hf.get("rope_scaling")
                or hf.get("use_sliding_window")
                or hf.get("hidden_act", "silu") != "silu"
                or hf.get("tie_word_embeddings")):
            raise ValueError(
                "only SDAR-MoE's own block is implemented (every layer an "
                "expert layer, the top k renormalised, no bias, no rotary "
                "scaling, no window, silu, an untied head)")
        return cls(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            rms_norm_eps=hf["rms_norm_eps"], rope_theta=hf["rope_theta"],
            max_seq_len=hf["max_position_embeddings"], **kw)

    @property
    def kv_row(self):
        """Values of one cached row: ``[k ; v]`` of every K/V head."""
        return 2 * self.num_kv_heads * self.head_dim

    @property
    def inv_freq(self):
        d = self.head_dim
        return self.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32)
                                   / d)


#: stacked over the layers, sliced a layer at a time
_LAYER = ("attn_norm_w", "ffn_norm_w", "q_w", "kv_w", "q_norm_w",
          "k_norm_w", "o_w", "router_w")
# ("expert_gu_w", "expert_down_w" are applied where they lie, all layers'
# experts as the groups of one product: kernels/moe.py)


def _mm(x, w):
    return jnp.matmul(x, w, precision=matmul_precision())


def param_shapes(c):
    """``{parameter: (shape, how it is drawn, dtype)}`` of a model of
    configuration ``c``: the constructor's table (and what a compile for
    a described chip builds its shapes from).  ``kv_w`` is ``[W_k | W_v]``
    (a token's cached row as one product), ``expert_gu_w`` the gate beside
    the up projection."""
    D, V, L = c.hidden_size, c.vocab_size, c.num_layers
    H, N, hd = c.num_heads, c.num_kv_heads, c.head_dim
    E, F = c.num_experts, c.moe_intermediate_size
    dt = c.dtype
    return {
        "wte": ((V, D), "normal", dt), "lnf_w": ((D,), "ones", dt),
        "lm_head": ((D, V), "normal", dt),
        "attn_norm_w": ((L, D), "ones", dt),
        "ffn_norm_w": ((L, D), "ones", dt),
        "q_w": ((L, D, H * hd), "normal", dt),
        "kv_w": ((L, D, 2 * N * hd), "normal", dt),
        "q_norm_w": ((L, hd), "ones", dt),
        "k_norm_w": ((L, hd), "ones", dt),
        "o_w": ((L, H * hd, D), "normal", dt),
        "router_w": ((L, D, E), "normal", dt),
        "expert_gu_w": ((L, E, D, 2 * F), "normal", dt),
        "expert_down_w": ((L, E, F, D), "normal", dt),
    }


@functools.partial(jax.jit, donate_argnums=0)
def _put_layer(stacked, layer, i):
    return jax.lax.dynamic_update_index_in_dim(stacked, layer, i, 0)


class SdarMoeForCausalLM(Layer):
    """``tensors``, where given, is a loader: ``tensors(name)`` hands back
    the parameter ``name`` of ``param_shapes(config)`` at its shape and
    dtype, and the constructor draws nothing (a checkpoint's 8.7 GB, or a
    benchmark's seeded weights, are not drawn first and replaced)."""

    def __init__(self, config: SdarConfig, tensors=None):
        t0_ns = time.perf_counter_ns()
        super().__init__()
        self.config = c = config
        _moe.preload(c.hidden_size, c.moe_intermediate_size, c.dtype)
        from ..nn.initializer import Constant, Normal
        from ..nn.functional.init_utils import param_attr_init
        from ..distributed.sharding_utils import annotate_param
        normal = Normal(0.0, c.initializer_range)

        def by_layer(shape, dtype):
            # a stacked tensor one layer (and one expert) at a time: an
            # eager draw takes about four times its float32 size, and six
            # layers' experts are 7 GB in bfloat16
            if len(shape) < 3:
                return normal(shape, dtype)
            out = jnp.zeros(shape, dtype)
            for i in range(shape[0]):
                out = _put_layer(out, by_layer(shape[1:], dtype), i)
            return out

        def given(name):
            def init(shape, dtype):
                x = tensors(name)
                if tuple(x.shape) != tuple(shape) or x.dtype != dtype:
                    raise ValueError(
                        f"{name}: the model holds {tuple(shape)} {dtype}, "
                        f"the loader gave {tuple(x.shape)} {x.dtype}")
                return x
            return init

        draw = {"normal": by_layer, "ones": Constant(1.0)}
        # the largest first, while nothing else is resident
        for name, (shape, how, dtype) in sorted(
                param_shapes(c).items(), key=lambda kv: -math.prod(kv[1][0])):
            p = param_attr_init(shape, jnp.dtype(dtype), None, False,
                                draw[how] if tensors is None else given(name))
            annotate_param(p, P())
            setattr(self, name, p)
        # what moe_load() has published so far
        self._moe_seen = {"assignments": 0, "tokens": 0}
        _trace.lifecycle_since("setup.model_init", t0_ns)

    # -- what the model caches -----------------------------------------------
    def cache_spec(self):
        """What a serving engine has to hold: for each of ``kv_layers``
        layers one row ``[k ; v]`` of ``kv_row`` values per token, with no
        head axis (``kv_heads`` 0), no per-slot state, the ``step_state``
        arrays ``(shape, dtype)`` that every serving program takes and
        hands on (the live (token, expert) pairs each expert of each layer
        has taken, and the live tokens routed), and ``decode_block``: a
        decode launch is one pass over ``length`` positions a row, which
        start as ``mask_token_id`` and are revealed in ``steps`` passes
        unless a request says otherwise."""
        c = self.config
        return {
            "kv_layers": c.num_layers, "kv_heads": 0, "head_dim": 0,
            "kv_row": c.kv_row, "slot_state": {},
            "step_state": {
                "moe_assignments": ((c.num_layers, c.num_experts), "int32"),
                "moe_tokens": ((), "int32"),
            },
            "decode_block": {"length": c.block_length,
                             "mask_token_id": c.mask_token_id,
                             "steps": c.denoising_steps},
        }

    def moe_load(self, state):
        """The expert layers' load so far from an engine's
        ``step_state()`` reading, as ``DeepseekV2ForCausalLM.moe_load``
        gives it: ``assignments``, ``tokens``, ``per_expert [layers,
        experts]`` and ``load_max_over_mean``.  Publishes what was added
        since the last call as the counters ``serving.moe.assignments``
        and ``serving.moe.tokens``, and the gauge
        ``serving.moe.load_max_over_mean``."""
        return _moe.publish_load(state, self._moe_seen)

    def decode_state(self):
        """Raw device weights for the serving programs (one pytree the
        engine passes through jit unchanged)."""
        return {n: getattr(self, n)._data for n in param_shapes(self.config)}

    # -- the block, once -----------------------------------------------------
    def _rope(self, x, pos):
        """``x [B, T, heads, hd]`` turned by its positions ``pos [B, T]``,
        each head's dims as two halves (dim ``i`` pairs with ``i +
        hd/2``); float32 arithmetic, ``x``'s dtype back."""
        ang = pos[..., None, None].astype(jnp.float32) * self.config.inv_freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1).astype(x.dtype)

    def _layers(self, w, h, pos, cache, attend, live=None):
        """Every layer over ``h [B, T, D]`` at positions ``pos [B, T]``.
        ``cache`` is ``(what attend carries, assignments, tokens)``;
        ``attend(carried, i, q, k, v) -> (o [B, T, H, hd], carried)`` for
        layer ``i``: ``q [B, T, H, hd]`` normalised, turned and scaled,
        ``k`` (normalised and turned) and ``v [B, T, n_kv, hd]``.
        ``live [B, T]`` bool leaves tokens out of the experts and their
        counts."""
        c = self.config
        B, T, D = h.shape
        H, N, hd = c.num_heads, c.num_kv_heads, c.head_dim
        live = jnp.ones((B, T), bool) if live is None else live
        scale = hd ** -0.5

        def body(carry, i):
            hh, carried, counts = carry
            # one layer's slice of each stacked weight, read where it lies
            lw = {k: jax.lax.dynamic_index_in_dim(w[k], i, 0, False)
                  for k in _LAYER}
            x = _rms(hh, lw["attn_norm_w"], c.rms_norm_eps)
            q = _rms(_mm(x, lw["q_w"]).reshape(B, T, H, hd), lw["q_norm_w"],
                     c.rms_norm_eps)
            kv = _mm(x, lw["kv_w"])
            k = _rms(kv[..., :N * hd].reshape(B, T, N, hd), lw["k_norm_w"],
                     c.rms_norm_eps)
            v = kv[..., N * hd:].reshape(B, T, N, hd)
            q = (self._rope(q, pos).astype(jnp.float32) * scale).astype(
                x.dtype)
            o, carried = attend(carried, i, q, self._rope(k, pos), v)
            hh = hh + _mm(o.reshape(B, T, H * hd).astype(x.dtype), lw["o_w"])
            z = _rms(hh, lw["ffn_norm_w"], c.rms_norm_eps)
            f, count = self._expert_ffn(w, lw, i, z.reshape(B * T, D),
                                        live.reshape(B * T))
            counts = jax.lax.dynamic_update_index_in_dim(
                counts, jax.lax.dynamic_index_in_dim(counts, i, 0, False)
                + count, i, 0)
            return (hh + f.reshape(B, T, D).astype(hh.dtype), carried,
                    counts), None

        carried, counts, tokens = cache
        (h, carried, counts), _ = jax.lax.scan(
            body, (h, carried, counts),
            jnp.arange(c.num_layers, dtype=jnp.int32))
        return h, (carried, counts, tokens + live.sum(dtype=tokens.dtype))

    def _expert_ffn(self, w, lw, i, z, live):
        """Layer ``i``'s experts over ``z [N, D]`` for the rows that are
        ``live``: the top ``k`` of a plain softmax (one routing group),
        renormalised.  Returns ``(f [N, D] float32, count [experts]
        int32)``."""
        c = self.config
        p, expert = _moe.group_limited_top_k(
            jnp.matmul(z, lw["router_w"], preferred_element_type=jnp.float32,
                       precision=matmul_precision()),
            1, 1, c.num_experts_per_tok)
        return _moe.held_expert_ffn(
            z, p / p.sum(-1, keepdims=True), expert, w["expert_gu_w"],
            w["expert_down_w"], 0, i, live)

    def _logits(self, w, h):
        h = _rms(h, w["lnf_w"], self.config.rms_norm_eps)
        return _mm(h, w["lm_head"]).astype(jnp.float32)

    def _no_counts(self):
        c = self.config
        return (jnp.zeros((c.num_layers, c.num_experts), jnp.int32),
                jnp.zeros((), jnp.int32))

    def _grouped(self, q):
        """``q [..., T, H, hd]`` -> ``[..., n_kv, G, T, hd]``."""
        c = self.config
        G = c.num_heads // c.num_kv_heads
        q = q.reshape(q.shape[:-2] + (c.num_kv_heads, G, c.head_dim))
        return jnp.moveaxis(q, -4, -2)

    # -- the plain forward pass ----------------------------------------------
    def forward(self, input_ids):
        """Logits ``[B, T, V]`` of whole sequences, no cache."""
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        return Tensor(self.forward_logits(self.decode_state(),
                                          jnp.asarray(ids)))

    def forward_logits(self, w, ids):
        """Block-causal attention over the whole sequence: logits ``[B,
        T, V]``, row ``i`` the model's reading of position ``i``."""
        c = self.config
        B, T = ids.shape
        blk = jnp.arange(T) // c.block_length
        seen = blk[None, :] <= blk[:, None]

        def attend(carried, i, q, k, v):
            s = jnp.einsum("bngqd,bknd->bngqk", self._grouped(q), k,
                           preferred_element_type=jnp.float32)
            p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
            o = jnp.einsum("bngqk,bknd->bqngd", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
            return o.reshape(B, T, c.num_heads, c.head_dim), carried

        pos = jnp.broadcast_to(jnp.arange(T), (B, T))
        h, _ = self._layers(w, jnp.take(w["wte"], ids, axis=0), pos,
                            ((),) + self._no_counts(), attend)
        return self._logits(w, h)

    # -- serving entry points (serving.block_decode.BlockDecodeLLMEngine) ----
    def prefill_paged(self, w, ids, start, length, bt, pool, state):
        """One chunked-prefill step: ``ids [1, C]`` holds ``length`` tokens
        of one request at positions ``[start, start + length)``, both whole
        blocks; ``bt`` is its block table, ``pool [kv_layers, n_blocks, bs,
        row]`` the pool, ``state`` the engine's ``step_state`` arrays.
        The chunk's rows are written first; its queries then attend under
        the block-causal mask over the live rows, a tile at a time into an
        online softmax.  Nothing is sampled: a prompt's last whole block
        predicts nothing (the first generated block is denoised from
        masks), so no logits are computed.  Returns ``(pool, state)``."""
        c = self.config
        B, C = ids.shape
        N, hd, Bl = c.num_kv_heads, c.head_dim, c.block_length
        G = c.num_heads // N
        bs, row = pool.shape[2], pool.shape[3]
        S = bt.shape[0] * bs
        valid = jnp.arange(C) < length
        tokpos = start + jnp.arange(C)
        blk = jnp.where(valid, bt[tokpos // bs], 0)   # padding: trash block
        off = tokpos % bs
        tile = min(_KEY_TILE, S)
        nb_tile = tile // bs
        n_tiles = -(-S // tile)
        btp = jnp.pad(bt, (0, n_tiles * nb_tile - bt.shape[0]))
        live_tiles = (start + length + tile - 1) // tile
        qblk = tokpos // Bl

        def attend(pool, i, q, k, v):
            line = jnp.concatenate([k[0].reshape(C, N * hd),
                                    v[0].reshape(C, N * hd)], -1)
            line = jnp.where(valid[:, None], line, 0)
            pool = pool.at[i, blk, off].set(jnp.pad(
                line, ((0, 0), (0, row - line.shape[-1]))).astype(
                    pool.dtype))
            qg = self._grouped(q[0]).astype(pool.dtype)    # [N, G, C, hd]

            def tile_of(t, st):
                m, l, acc = st
                blocks = jax.lax.dynamic_slice_in_dim(btp, t * nb_tile,
                                                      nb_tile)
                kt, vt = _ba._split(pool[i, blocks].reshape(tile, row),
                                    N, hd)
                s = jnp.einsum("ngqd,knd->ngqk", qg, kt,
                               preferred_element_type=jnp.float32)
                kblk = (t * tile + jnp.arange(tile)) // Bl
                s = jnp.where(kblk[None, :] <= qblk[:, None], s, NEG_INF)
                m_new = jnp.maximum(m, s.max(-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                acc = acc * alpha + jnp.einsum(
                    "ngqk,knd->ngqd", p.astype(vt.dtype), vt,
                    preferred_element_type=jnp.float32)
                return m_new, l * alpha + p.sum(-1, keepdims=True), acc

            _, l, acc = jax.lax.fori_loop(
                0, live_tiles, tile_of,
                (jnp.full((N, G, C, 1), NEG_INF, jnp.float32),
                 jnp.zeros((N, G, C, 1), jnp.float32),
                 jnp.zeros((N, G, C, hd), jnp.float32)))
            o = jnp.moveaxis(acc / l, 2, 0)                # [C, N, G, hd]
            return o.reshape(1, C, c.num_heads, hd), pool

        h = jnp.take(w["wte"], ids, axis=0)
        _, (pool, counts, tokens) = self._layers(
            w, h, tokpos[None], (pool, state["moe_assignments"],
                                 state["moe_tokens"]),
            attend, valid[None])
        return pool, {"moe_assignments": counts, "moe_tokens": tokens}

    def decode_paged(self, w, tok, pos, bt, pool, state, running, commit,
                     kernel=None):
        """One pass over the current block of ``S`` slot rows: ``tok [S,
        B]`` the blocks as they stand (mask tokens where nothing is
        revealed yet), ``pos [S]`` their first positions, ``bt [S,
        max_blocks]``, the pool and ``state`` as in :meth:`prefill_paged`,
        ``running [S]`` and ``commit [S]`` bool.  Every position attends
        to the row's cached prefix (positions below ``pos``) and to all
        ``B`` of the block's own fresh K/V.  Only a row in its ``commit``
        pass writes them to its own pool blocks; every other row's go to
        the trash block, so the pool holds committed tokens alone.  A row
        that is not running is tabled to the trash block by the engine and
        is left out of the experts.  ``kernel="pallas"`` walks the block
        tables (``kernels.block_attention.block_decode_attn``); otherwise
        the XLA gather twin.  Returns ``(logits [S, B, V], pool,
        state)``."""
        c = self.config
        S, Bl = tok.shape
        N, hd = c.num_kv_heads, c.head_dim
        bs, row = pool.shape[2], pool.shape[3]
        write = commit & running
        blk = jnp.where(write, bt[jnp.arange(S), pos // bs], 0)[:, None]
        off = (pos % bs)[:, None] + jnp.arange(Bl)
        if kernel not in (None, "off", "pallas"):
            raise ValueError(f"kernel={kernel!r}")
        walk = (_ba.block_decode_attn if kernel == "pallas"
                else _ba.block_decode_attn_xla)
        tokpos = pos[:, None] + jnp.arange(Bl)

        def attend(pool, i, q, k, v):
            line = jnp.concatenate([k.reshape(S, Bl, N * hd),
                                    v.reshape(S, Bl, N * hd)], -1)
            line = jnp.pad(line, ((0, 0), (0, 0),
                                  (0, row - line.shape[-1]))).astype(
                                      pool.dtype)
            # [S, N, G * B, hd]: a K/V head's query tile
            qt = self._grouped(q).reshape(S, N, -1, hd)
            o = walk(qt, line, pool, i, bt, pos, N)
            o = jnp.moveaxis(o.reshape(S, N, -1, Bl, hd), 3, 1)
            pool = pool.at[i, blk, off].set(line)
            return o.reshape(S, Bl, c.num_heads, hd), pool

        h = jnp.take(w["wte"], tok, axis=0)
        h, (pool, counts, tokens) = self._layers(
            w, h, tokpos, (pool, state["moe_assignments"],
                           state["moe_tokens"]),
            attend, jnp.broadcast_to(running[:, None], (S, Bl)))
        return (self._logits(w, h), pool,
                {"moe_assignments": counts, "moe_tokens": tokens})
