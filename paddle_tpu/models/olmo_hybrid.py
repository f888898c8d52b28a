"""Olmo-Hybrid: Gated DeltaNet layers beside full attention.

The published configuration (``model_type: olmo_hybrid``) interleaves
``linear_attention`` layers — the gated delta rule of
``kernels/gated_delta.py`` behind a short causal convolution — with
``full_attention`` layers in a repeating ``layer_types`` pattern.  The
block is OLMo 2's (arXiv:2501.00656): no norm in front of a sub-layer, an
RMSNorm on its output, ``h = x + RMSNorm(Mixer(x))`` then
``y = h + RMSNorm(MLP(h))`` with a gated (SwiGLU) MLP; QK-norm over the
whole width in the attention layers; an untied output head.  The source
gives ``rope_theta: null``: no rotary embedding is applied, position
reaches the attention layers through the recurrent layers below them.

A linear layer caches a fixed-size state per request (``[H, d_k, d_v]``
float32 and the last ``W - 1`` inputs of the convolution); a full layer
caches paged K/V.  :meth:`OlmoHybridForCausalLM.cache_spec` tells the
serving engine both, and the engine keeps them side by side in one
manager (``serving/paged.py``).

The block is written once (:meth:`_layers`): the forward pass, the chunked
prefill and the decode step differ only in the two functions that reach
the cache, one per kind of layer.  Layers are stacked per kind and scanned
by period of the pattern, so compile time does not grow with depth.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.dispatch import matmul_precision
from ..core.tensor import Tensor
from ..kernels import gated_delta as _gd
from ..kernels import paged_attention as _pa
from ..kernels._shapes import NEG_INF
from ..kernels.rms_norm import rms_norm_reference as _rms
from ..nn.layer.layers import Layer
from ..profiler import host_tracer as _trace

LINEAR, FULL = "linear_attention", "full_attention"

#: key positions a prefill chunk attends to per step of its walk over the
#: live K/V (a whole number of blocks)
_KEY_TILE = 1024


class OlmoHybridConfig:
    """The sizes of an ``olmo_hybrid`` model, under this package's names
    (``from_hf`` takes the published ``config.json`` keys)."""

    def __init__(self, vocab_size=100352, hidden_size=3840,
                 intermediate_size=11008, num_layers=32, layer_types=None,
                 num_heads=30, linear_num_heads=30, linear_key_head_dim=96,
                 linear_value_head_dim=192, linear_conv_kernel_dim=4,
                 rms_norm_eps=1e-6, max_seq_len=65536,
                 initializer_range=0.02, dtype="float32"):
        if layer_types is None:
            layer_types = [FULL if i % 4 == 3 else LINEAR
                           for i in range(num_layers)]
        layer_types = list(layer_types)
        if len(layer_types) != num_layers or set(layer_types) - {LINEAR,
                                                                 FULL}:
            raise ValueError("layer_types must name one of "
                             f"{LINEAR!r}/{FULL!r} for each of the "
                             f"{num_layers} layers")
        if hidden_size % num_heads:
            raise ValueError("hidden_size is not a whole number of heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_layers = num_layers
        self.layer_types = layer_types
        self.num_heads = num_heads
        self.linear_num_heads = linear_num_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.rms_norm_eps = rms_norm_eps
        self.max_seq_len = max_seq_len
        self.initializer_range = initializer_range
        self.dtype = dtype
        # what the serving engine asks of any model's config: positions
        # past ``max_seq_len`` are refused unless they are rotary
        self.use_rope = False

    @classmethod
    def from_hf(cls, hf, **kw):
        """From the keys of the published ``config.json``."""
        if hf["linear_num_key_heads"] != hf["linear_num_value_heads"]:
            raise ValueError("grouped linear-attention heads are not "
                             "implemented (key heads != value heads)")
        if hf["num_key_value_heads"] != hf["num_attention_heads"]:
            raise ValueError("grouped-query attention is not implemented")
        return cls(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            layer_types=hf["layer_types"],
            num_heads=hf["num_attention_heads"],
            linear_num_heads=hf["linear_num_value_heads"],
            linear_key_head_dim=hf["linear_key_head_dim"],
            linear_value_head_dim=hf["linear_value_head_dim"],
            linear_conv_kernel_dim=hf["linear_conv_kernel_dim"],
            rms_norm_eps=hf["rms_norm_eps"],
            max_seq_len=hf["max_position_embeddings"], **kw)

    @property
    def period(self):
        """The shortest prefix of ``layer_types`` that the whole list
        repeats (the list itself when it repeats nothing)."""
        lt = self.layer_types
        for p in range(1, len(lt) + 1):
            if len(lt) % p == 0 and lt == lt[:p] * (len(lt) // p):
                return lt[:p]

    @property
    def conv_channels(self):
        return self.linear_num_heads * (2 * self.linear_key_head_dim
                                        + self.linear_value_head_dim)


#: stacked over all layers / the full-attention layers / the linear layers
_COMMON = ("mixer_norm_w", "mlp_norm_w", "gate_w", "up_w", "down_w")
_FULL = ("att_qkv_w", "att_o_w", "att_qnorm_w", "att_knorm_w")
_LINEAR = ("lin_qkv_w", "lin_g_w", "lin_ab_w", "lin_o_w", "lin_conv_w",
           "lin_A_log", "lin_dt_bias", "lin_norm_w")


def _mm(x, w):
    return jnp.matmul(x, w, precision=matmul_precision())


def param_shapes(c):
    """``{parameter: (shape, how it is drawn, dtype)}`` of a model of
    configuration ``c``: the constructor's table (and what a compile for
    a described chip builds its shapes from)."""
    D, F, V, L = (c.hidden_size, c.intermediate_size, c.vocab_size,
                  c.num_layers)
    nF = c.layer_types.count(FULL)
    nL = L - nF
    H, dv = c.linear_num_heads, c.linear_value_head_dim
    W, ch = c.linear_conv_kernel_dim, c.conv_channels
    dt = c.dtype
    return {
        "wte": ((V, D), "normal", dt), "lnf_w": ((D,), "ones", dt),
        "lm_head": ((D, V), "normal", dt),
        "mixer_norm_w": ((L, D), "ones", dt),
        "mlp_norm_w": ((L, D), "ones", dt),
        "gate_w": ((L, D, F), "normal", dt),
        "up_w": ((L, D, F), "normal", dt),
        "down_w": ((L, F, D), "normal", dt),
        "att_qkv_w": ((nF, D, 3 * D), "normal", dt),
        "att_o_w": ((nF, D, D), "normal", dt),
        "att_qnorm_w": ((nF, D), "ones", dt),
        "att_knorm_w": ((nF, D), "ones", dt),
        "lin_qkv_w": ((nL, D, ch), "normal", dt),
        "lin_g_w": ((nL, D, H * dv), "normal", dt),
        "lin_ab_w": ((nL, D, 2 * H), "normal", dt),
        "lin_o_w": ((nL, H * dv, D), "normal", dt),
        "lin_conv_w": ((nL, W, ch), "conv", dt),
        "lin_A_log": ((nL, H), "A_log", "float32"),
        "lin_dt_bias": ((nL, H), "dt_bias", "float32"),
        "lin_norm_w": ((nL, dv), "ones", dt),
    }


class OlmoHybridForCausalLM(Layer):
    def __init__(self, config: OlmoHybridConfig):
        t0_ns = time.perf_counter_ns()
        super().__init__()
        self.config = c = config
        from ..nn.initializer import Constant, Normal, Uniform
        from ..nn.functional.init_utils import param_attr_init
        from ..distributed.sharding_utils import annotate_param
        W = c.linear_conv_kernel_dim
        # the short convolution as torch's Conv1d draws it; the decay as
        # flash-linear-attention's GatedDeltaNet draws it: A ~ U(0, 16],
        # dt log-uniform in [1e-3, 1e-1], dt_bias its inverse softplus
        draw = {"normal": Normal(0.0, c.initializer_range),
                "ones": Constant(1.0),
                "conv": Uniform(-W ** -0.5, W ** -0.5),
                "A_log": lambda s, d: jnp.log(
                    16.0 - Uniform(0.0, 16.0)(s, d)),
                "dt_bias": _dt_bias_init}
        # the largest first: an eager draw takes about four times its
        # float32 size beside what is already resident (15.1 GB of a 16.9 GB
        # chip at the published widths when the MLP's leaves came late)
        for name, (shape, how, dtype) in sorted(
                param_shapes(c).items(), key=lambda kv: -math.prod(kv[1][0])):
            p = param_attr_init(shape, jnp.dtype(dtype), None, False,
                                draw[how])
            annotate_param(p, P())
            setattr(self, name, p)
        _trace.lifecycle_since("setup.model_init", t0_ns)

    # -- what the model caches -----------------------------------------------
    def cache_spec(self):
        """What a serving engine has to hold for one request: paged K/V
        for ``kv_layers`` layers of ``kv_heads`` heads of ``head_dim``,
        and one row per slot of each ``slot_state`` array, given as
        ``(leading shape, shape per slot, dtype)`` — the slot axis comes
        between the two."""
        c = self.config
        nF = c.layer_types.count(FULL)
        nL = c.num_layers - nF
        return {
            "kv_layers": nF, "kv_heads": c.num_heads,
            "head_dim": c.hidden_size // c.num_heads,
            "slot_state": {
                "gdn_state": ((nL,), (c.linear_num_heads,
                                      c.linear_key_head_dim,
                                      c.linear_value_head_dim), "float32"),
                "gdn_conv": ((nL,), (c.linear_conv_kernel_dim - 1,
                                     c.conv_channels), c.dtype),
            },
        }

    def decode_state(self):
        """Raw device weights for the serving programs (one pytree the
        engine passes through jit unchanged)."""
        return {n: getattr(self, n)._data for n in param_shapes(self.config)}

    # -- the block, once -----------------------------------------------------
    def _layers(self, w, h, cache, attend, recur):
        """Every layer over ``h [B, T, D]``.  ``cache`` is whatever the
        two cache functions carry from layer to layer (pools, states; an
        empty tuple for the plain forward pass):

        * ``attend(cache, i, q, k, v) -> (o, cache)`` for full layer
          ``i``: ``q, k, v [B, T, nh, hd]`` (``q`` scaled), ``o`` alike;
        * ``recur(cache, i, x, conv_w, mix, g, beta) -> (o, cache)`` for
          linear layer ``i``: ``x [B, T, C]`` is the input of the causal
          convolution (whose tail is cached), ``mix`` turns its output
          into the rule's ``q, k, v``, ``g, beta [B, T, H]`` are the log
          decay and the write strength; ``o [B, T, H, dv]``.
        """
        c = self.config
        period = c.period
        n_periods = c.num_layers // len(period)
        per = {FULL: period.count(FULL), LINEAR: period.count(LINEAR)}

        def at(names, i):
            # one layer's slice of each stacked weight, read where it lies
            return {k: jax.lax.dynamic_index_in_dim(w[k], i, 0, False)
                    for k in names}

        def body(carry, p):
            hh, cache = carry
            seen = {FULL: 0, LINEAR: 0}
            for j, kind in enumerate(period):
                lw = at(_COMMON, p * len(period) + j)
                i = p * per[kind] + seen[kind]
                seen[kind] += 1
                if kind == FULL:
                    a, cache = self._full_mixer(c, at(_FULL, i), hh, cache,
                                                i, attend)
                else:
                    a, cache = self._linear_mixer(c, at(_LINEAR, i), hh,
                                                  cache, i, recur)
                hh = hh + _rms(a, lw["mixer_norm_w"], c.rms_norm_eps)
                f = _mm(jax.nn.silu(_mm(hh, lw["gate_w"]))
                        * _mm(hh, lw["up_w"]), lw["down_w"])
                hh = hh + _rms(f, lw["mlp_norm_w"], c.rms_norm_eps)
            return (hh, cache), None

        xs = jnp.arange(n_periods, dtype=jnp.int32)
        (h, cache), _ = jax.lax.scan(body, (h, cache), xs)
        return h, cache

    @staticmethod
    def _full_mixer(c, kw, x, cache, i, attend):
        B, T, D = x.shape
        nh = c.num_heads
        hd = D // nh
        q, k, v = jnp.split(_mm(x, kw["att_qkv_w"]), 3, axis=-1)
        q = _rms(q, kw["att_qnorm_w"], c.rms_norm_eps)
        k = _rms(k, kw["att_knorm_w"], c.rms_norm_eps)
        heads = lambda t: t.reshape(B, T, nh, hd)            # noqa: E731
        o, cache = attend(cache, i, heads(q) * (1.0 / math.sqrt(hd)),
                          heads(k), heads(v))
        return _mm(o.reshape(B, T, D).astype(x.dtype), kw["att_o_w"]), cache

    @staticmethod
    def _linear_mixer(c, kw, x, cache, i, recur):
        B, T, _ = x.shape
        H, dk, dv = (c.linear_num_heads, c.linear_key_head_dim,
                     c.linear_value_head_dim)
        f32 = jnp.float32
        a, b = jnp.split(_mm(x, kw["lin_ab_w"]).astype(f32), 2, axis=-1)
        beta = 2.0 * jax.nn.sigmoid(b)        # linear_allow_neg_eigval
        g = -jnp.exp(kw["lin_A_log"].astype(f32)) * jax.nn.softplus(
            a + kw["lin_dt_bias"].astype(f32))

        def mix(y):
            """The convolution's output ``[B, T, C]`` as the rule's
            ``q, k, v`` (float32; L2-normalised ``q``, ``k``)."""
            y = jax.nn.silu(y.astype(f32))
            q, k, v = jnp.split(y, [H * dk, 2 * H * dk], axis=-1)
            q, k = q.reshape(B, T, H, dk), k.reshape(B, T, H, dk)
            unit = lambda t: t * jax.lax.rsqrt(                # noqa: E731
                jnp.sum(t * t, -1, keepdims=True) + 1e-6)
            return (unit(q) * dk ** -0.5, unit(k),
                    v.reshape(B, T, H, dv))

        o, cache = recur(cache, i, _mm(x, kw["lin_qkv_w"]),
                         kw["lin_conv_w"], mix, g, beta)
        o = _rms(o, kw["lin_norm_w"].astype(f32), c.rms_norm_eps)
        z = jax.nn.silu(_mm(x, kw["lin_g_w"]).astype(f32))
        o = o.reshape(B, T, H * dv) * z
        return _mm(o.astype(x.dtype), kw["lin_o_w"]), cache

    def _logits(self, w, h_last):
        h_last = _rms(h_last, w["lnf_w"], self.config.rms_norm_eps)
        return _mm(h_last, w["lm_head"]).astype(jnp.float32)

    # -- the plain forward pass ----------------------------------------------
    def forward(self, input_ids):
        """Logits ``[B, T, V]`` of whole sequences, no cache."""
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        return Tensor(self.forward_logits(self.decode_state(),
                                          jnp.asarray(ids)))

    def forward_logits(self, w, ids):
        c = self.config
        B, T = ids.shape
        Tp = -(-T // _gd.CHUNK) * _gd.CHUNK if T > _gd.CHUNK else T
        live = (jnp.arange(Tp) < T)[None, :, None]
        causal = jnp.tril(jnp.ones((T, T), bool))

        def attend(cache, i, q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                           preferred_element_type=jnp.float32)
            p = jax.nn.softmax(jnp.where(causal, s, NEG_INF), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32), cache

        def recur(cache, i, x, conv_w, mix, g, beta):
            tail = jnp.zeros((B, conv_w.shape[0] - 1, x.shape[-1]), x.dtype)
            y, _ = _gd.causal_conv(x, conv_w, tail)
            pad = lambda t: jnp.pad(                           # noqa: E731
                t, ((0, 0), (0, Tp - T)) + ((0, 0),) * (t.ndim - 2))
            q, k, v = map(pad, mix(y))
            s0 = jnp.zeros((B,) + q.shape[2:] + v.shape[-1:], jnp.float32)
            o, _ = _gd.gdn_chunk(q, k, v, jnp.where(live, pad(g), 0.0),
                                 jnp.where(live, pad(beta), 0.0), s0)
            return o[:, :T], cache

        h, _ = self._layers(w, jnp.take(w["wte"], ids, axis=0), (), attend,
                            recur)
        return self._logits(w, h)

    # -- serving entry points (paddle_tpu.serving.LLMEngine, paged) ----------
    def prefill_paged(self, w, ids, start, length, bt, pool_k, pool_v,
                      state, slot, kernel=None):
        """One chunked-prefill step: ``ids [1, C]`` holds ``length`` tokens
        of one request at positions ``[start, start + length)``; ``bt`` is
        its block table, ``pool_k``/``pool_v`` the stacked pools of the
        full layers ``[kv_layers, n_blocks, bs, nhp, hd]``, ``state`` the
        engine's per-slot arrays (see :meth:`cache_spec`), ``slot`` the
        request's row in them.  The row is taken as zero when ``start ==
        0`` (a slot's last owner leaves nothing behind), advanced over the
        ``length`` live positions only, and written back.  Returns
        ``(pool_k, pool_v, state, logits [1, V])`` with the logits read at
        the chunk's last live token.  ``kernel`` is the engine's choice
        for the cache's kernels, as :meth:`decode_paged` takes it; this
        family's prefill has one form and ignores it."""
        c = self.config
        B, C = ids.shape
        nh = c.num_heads
        nhp, bs = pool_k.shape[3], pool_k.shape[2]
        pad, _ = _pa.head_padding(pool_k, nh)
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
        fresh = start == 0
        vm = valid[:, None, None]

        def attend(cache, i, q, k, v):
            ck, cv, *rest = cache
            ck = ck.at[i, blk, off].set(
                pad(jnp.where(vm, k[0], 0)).astype(ck.dtype))
            cv = cv.at[i, blk, off].set(
                pad(jnp.where(vm, v[0], 0)).astype(cv.dtype))
            qh = jnp.swapaxes(q[0], 0, 1).astype(ck.dtype)    # [nh, C, hd]

            def fold(t, carry):
                # one tile of live keys, folded into the online softmax
                m, l, acc = carry
                blocks = jax.lax.dynamic_slice_in_dim(btp, t * nb_tile,
                                                      nb_tile)
                kt = ck[i, blocks].reshape(tile, nhp, -1)[:, :nh]
                vt = cv[i, blocks].reshape(tile, nhp, -1)[:, :nh]
                s = jnp.einsum("hqd,khd->hqk", qh, kt,
                               preferred_element_type=jnp.float32)
                kpos = t * tile + jnp.arange(tile)
                s = jnp.where(kpos[None, None, :] <= tokpos[None, :, None],
                              s, NEG_INF)
                m_new = jnp.maximum(m, s.max(-1, keepdims=True))
                p = jnp.exp(s - m_new)
                scale = jnp.exp(m - m_new)
                acc = acc * scale + jnp.einsum(
                    "hqk,khd->hqd", p.astype(vt.dtype), vt,
                    preferred_element_type=jnp.float32)
                return m_new, l * scale + p.sum(-1, keepdims=True), acc

            hd = q.shape[-1]
            _, l, acc = jax.lax.fori_loop(
                0, live_tiles, fold,
                (jnp.full((nh, C, 1), NEG_INF, jnp.float32),
                 jnp.zeros((nh, C, 1), jnp.float32),
                 jnp.zeros((nh, C, hd), jnp.float32)))
            return jnp.swapaxes(acc / l, 0, 1)[None], (ck, cv, *rest)

        def recur(cache, i, x, conv_w, mix, g, beta):
            ck, cv, st, tails = cache
            row = lambda a: jax.lax.dynamic_slice(                 # noqa: E731
                a, (i, slot) + (0,) * (a.ndim - 2),
                (1, 1) + a.shape[2:])[0]
            s0 = jnp.where(fresh, 0.0, row(st).astype(jnp.float32))
            tail = jnp.where(fresh, jnp.zeros((), tails.dtype), row(tails))
            y, tail = _gd.causal_conv(x, conv_w, tail, length)
            q, k, v = mix(y)
            live = valid[None, :, None]
            o, s1 = _gd.gdn_chunk(q, k, v, jnp.where(live, g, 0.0),
                                  jnp.where(live, beta, 0.0), s0)
            put = lambda a, r: jax.lax.dynamic_update_slice(       # noqa: E731
                a, r[None].astype(a.dtype), (i, slot) + (0,) * (a.ndim - 2))
            return o, (ck, cv, put(st, s1), put(tails, tail))

        h = jnp.take(w["wte"], ids, axis=0)
        h, (pool_k, pool_v, st, tails) = self._layers(
            w, h, (pool_k, pool_v, state["gdn_state"], state["gdn_conv"]),
            attend, recur)
        h_last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
        return (pool_k, pool_v, {"gdn_state": st, "gdn_conv": tails},
                self._logits(w, h_last[:, 0]))

    def decode_paged(self, w, tok, pos, bt, pool_k, pool_v, state, running,
                     kernel=None):
        """One decode step for ``B`` slot rows: ``tok``/``pos [B]``,
        ``bt [B, max_blocks]``, the pools and ``state`` as in
        :meth:`prefill_paged`, ``running [B]`` bool.  A row that is not
        running (free, or between two prefill chunks) is tabled to the
        trash block by the engine and keeps its ``state`` rows bit for
        bit.  ``kernel="pallas"`` runs the full layers' attention through
        the block-table walk of ``kernels.paged_attention``; otherwise the
        XLA gather twin.  Returns ``(logits [B, V], pool_k, pool_v,
        state)``."""
        c = self.config
        B = tok.shape[0]
        nh = c.num_heads
        nhp, bs = pool_k.shape[3], pool_k.shape[2]
        pad, unpad = _pa.head_padding(pool_k, nh)
        S = bt.shape[1] * bs
        mask = jnp.arange(S)[None, :] <= pos[:, None]
        blk = bt[jnp.arange(B), pos // bs]
        off = pos % bs
        mode = kernel or "off"
        if mode not in ("off", "pallas"):
            raise ValueError(f"decode_paged: kernel={mode!r}")
        _pa.note_program(mode)

        def attend(cache, i, q, k, v):
            ck, cv, *rest = cache
            ck = ck.at[i, blk, off].set(pad(k[:, 0]).astype(ck.dtype))
            cv = cv.at[i, blk, off].set(pad(v[:, 0]).astype(cv.dtype))
            if mode == "pallas":
                # q is already scaled
                o = unpad(_pa.paged_decode_attention(
                    pad(q[:, 0]), ck, cv, i, bt, pos, scale=1.0))
            else:
                gk = unpad(ck[i, bt].reshape(B, S, nhp, -1))
                gv = unpad(cv[i, bt].reshape(B, S, nhp, -1))
                s = jnp.einsum("bhd,bkhd->bhk", q[:, 0].astype(gk.dtype),
                               gk, preferred_element_type=jnp.float32)
                p = jax.nn.softmax(
                    jnp.where(mask[:, None, :], s, NEG_INF), axis=-1)
                o = jnp.einsum("bhk,bkhd->bhd", p.astype(gv.dtype), gv,
                               preferred_element_type=jnp.float32)
            return o[:, None], (ck, cv, *rest)

        def recur(cache, i, x, conv_w, mix, g, beta):
            ck, cv, st, tails = cache
            y, tail = _gd.causal_conv(x, conv_w, tails[i])
            q, k, v = mix(y)
            o, s1 = _gd.gdn_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], st[i].astype(jnp.float32))
            keep = lambda new, old: jnp.where(                     # noqa: E731
                running.reshape((B,) + (1,) * (old.ndim - 1)),
                new.astype(old.dtype), old)
            return o[:, None], (ck, cv, st.at[i].set(keep(s1, st[i])),
                                tails.at[i].set(keep(tail, tails[i])))

        h = jnp.take(w["wte"], tok, axis=0)[:, None, :]
        h, (pool_k, pool_v, st, tails) = self._layers(
            w, h, (pool_k, pool_v, state["gdn_state"], state["gdn_conv"]),
            attend, recur)
        return (self._logits(w, h[:, 0]), pool_k, pool_v,
                {"gdn_state": st, "gdn_conv": tails})


def _dt_bias_init(shape, dtype):
    from ..nn.initializer import Uniform
    dt = jnp.exp(Uniform(math.log(1e-3), math.log(1e-1))(shape, jnp.float32))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
