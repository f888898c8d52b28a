"""Jamba (``model_type: jamba``): Mamba-1 selective-state layers beside
attention layers with one K/V head.

The published configuration (AI21 Jamba; the ``config.json`` keys under
their own names in :meth:`JambaConfig.from_hf`) is a pre-norm RMSNorm
decoder, ``r = x + Mixer(RMSNorm(x))``, ``y = r + MLP(RMSNorm(r))`` with a
gated MLP ``(silu(h W_g) * h W_u) W_d``, a final RMSNorm and a head tied to
the embedding.  Layer ``i`` is an attention layer where ``i %
attn_layer_period == attn_layer_offset``, a Mamba layer otherwise.  With
``num_experts`` 1 every MLP is dense (an expert layer of one expert).

* Attention: ``num_heads`` query heads share ``num_kv_heads`` K/V heads
  of ``hidden_size / num_heads``, causal softmax, NO positional encoding
  (the Mamba layers below carry position), no bias.
* Mamba: ``[x ; z] = u W_in`` (``D -> 2E``); ``x`` through a depthwise
  causal convolution of ``d_conv`` taps with a bias and SiLU; ``[d ; B ;
  C] = x W_x`` (``E -> R + 2N``), each through its own RMSNorm (Jamba's
  inner norms); ``dt = softplus(d W_dt + b_dt)``; ``A = -exp(A_log)``;
  the selective scan of ``kernels/selective_scan.py`` with the skip
  ``D``; ``(y * silu(z)) W_out``.

:meth:`JambaForCausalLM.cache_spec` tells the serving engine what it
caches: one row ``[k ; v]`` a token for the attention layers (``kv_row``,
paged as usual, read by ``kernels.window_attention``'s walks with a full
band), and per slot each Mamba layer's state ``[N, E]`` float32 and the
last ``d_conv - 1`` inputs of its convolution.

The block is written once (:meth:`_layers`), as in ``olmo_hybrid.py``: the
forward pass, the chunked prefill and the decode step differ only in the
two functions that reach the cache.  Layers are stacked per kind and
scanned by period of the pattern, so compile time does not grow with
depth.
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
from ..kernels import selective_scan as _ss
from ..kernels import window_attention as _wa
from ..kernels._shapes import NEG_INF
from ..kernels.rms_norm import rms_norm_reference as _rms
from ..nn.layer.layers import Layer
from ..profiler import host_tracer as _trace

MAMBA, ATTN = "mamba", "attention"


class JambaConfig:
    """The sizes of a ``jamba`` model under this package's names
    (``from_hf`` takes the published ``config.json`` keys)."""

    def __init__(self, vocab_size=65536, hidden_size=2560,
                 intermediate_size=8192, num_layers=28, num_heads=20,
                 num_kv_heads=1, attn_layer_offset=7, attn_layer_period=14,
                 d_state=16, d_conv=4, expand=2, dt_rank=160,
                 rms_norm_eps=1e-6, max_seq_len=262144,
                 initializer_range=0.02, dtype="float32"):
        if hidden_size % num_heads or num_heads % num_kv_heads:
            raise ValueError("hidden_size must be whole query heads, and "
                             "the query heads whole groups of K/V heads")
        if not 0 <= attn_layer_offset < attn_layer_period:
            raise ValueError("attn_layer_offset must lie inside a period")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = hidden_size // num_heads
        self.layer_types = [
            ATTN if i % attn_layer_period == attn_layer_offset else MAMBA
            for i in range(num_layers)]
        self.period = attn_layer_period
        self.d_state = d_state
        self.d_conv = d_conv
        self.inner = expand * hidden_size
        self.dt_rank = dt_rank
        self.rms_norm_eps = rms_norm_eps
        self.max_seq_len = max_seq_len
        self.initializer_range = initializer_range
        self.dtype = dtype
        # what the serving engine asks of any model's config: positions
        # past ``max_seq_len`` are refused unless they are rotary
        self.use_rope = False

    @classmethod
    def from_hf(cls, hf, **kw):
        """From the keys of the published ``config.json``.  Refuses what is
        not implemented: expert layers (``num_experts > 1``) and window
        attention (a ``sliding_window``), and any other mixer or head
        than Jamba2's (a bias on the convolution and none on the
        projections, silu, a tied head)."""
        if hf.get("num_experts", 1) > 1:
            raise ValueError(
                f"num_experts {hf['num_experts']}: Jamba's expert layers are "
                "not implemented (only num_experts 1, a dense MLP in every "
                "layer)")
        if hf.get("sliding_window") is not None:
            raise ValueError(
                f"sliding_window {hf['sliding_window']}: window attention "
                "is not implemented for jamba (only full causal attention)")
        if (not hf.get("mamba_conv_bias", True)
                or hf.get("mamba_proj_bias", False)
                or hf.get("hidden_act", "silu") != "silu"
                or not hf.get("tie_word_embeddings", False)):
            raise ValueError("only Jamba2's own block is implemented (a bias "
                             "on the convolution, none on the projections, "
                             "silu, a tied head)")
        return cls(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            attn_layer_offset=hf["attn_layer_offset"],
            attn_layer_period=hf["attn_layer_period"],
            d_state=hf["mamba_d_state"], d_conv=hf["mamba_d_conv"],
            expand=hf["mamba_expand"], dt_rank=hf["mamba_dt_rank"],
            rms_norm_eps=hf["rms_norm_eps"],
            max_seq_len=hf["max_position_embeddings"], **kw)

    @property
    def kv_row(self):
        """Values of one cached row: ``[k ; v]`` of every K/V head."""
        return 2 * self.num_kv_heads * self.head_dim


#: stacked over all layers / the attention layers / the Mamba layers
_COMMON = ("mixer_norm_w", "mlp_norm_w", "gu_w", "down_w")
_ATTN = ("qkv_w", "o_w")
_MAMBA = ("in_w", "conv_w", "conv_b", "x_w", "dt_norm_w", "b_norm_w",
          "c_norm_w", "dt_w", "dt_b", "A_log", "D", "out_w")


def _mm(x, w):
    return jnp.matmul(x, w, precision=matmul_precision())


def _mm32(x, w):
    """``x @ w`` in ``w``'s type, accumulated and returned in float32."""
    return jnp.matmul(x.astype(w.dtype), w, precision=matmul_precision(),
                      preferred_element_type=jnp.float32)


def param_shapes(c):
    """``{parameter: (shape, how it is drawn, dtype)}`` of a model of
    configuration ``c``: the constructor's table (and what a compile for a
    described chip builds its shapes from).  ``gu_w`` is the MLP's gate
    beside its up projection, ``qkv_w`` is ``[W_q | W_k | W_v]``;
    ``A_log`` is ``[N, E]`` (channels last), and it, ``dt_b`` and ``D``
    are float32."""
    D, V, L, F = c.hidden_size, c.vocab_size, c.num_layers, c.intermediate_size
    nA = c.layer_types.count(ATTN)
    nM = L - nA
    E, N, R, W = c.inner, c.d_state, c.dt_rank, c.d_conv
    q, kv = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
    dt = c.dtype
    return {
        "wte": ((V, D), "normal", dt), "lnf_w": ((D,), "ones", dt),
        "mixer_norm_w": ((L, D), "ones", dt),
        "mlp_norm_w": ((L, D), "ones", dt),
        "gu_w": ((L, D, 2 * F), "normal", dt),
        "down_w": ((L, F, D), "normal", dt),
        "qkv_w": ((nA, D, q + 2 * kv), "normal", dt),
        "o_w": ((nA, q, D), "normal", dt),
        "in_w": ((nM, D, 2 * E), "normal", dt),
        "conv_w": ((nM, W, E), "conv", dt),
        "conv_b": ((nM, E), "conv", dt),
        "x_w": ((nM, E, R + 2 * N), "normal", dt),
        "dt_norm_w": ((nM, R), "ones", dt),
        "b_norm_w": ((nM, N), "ones", dt),
        "c_norm_w": ((nM, N), "ones", dt),
        "dt_w": ((nM, R, E), "normal", dt),
        "dt_b": ((nM, E), "dt_bias", "float32"),
        "A_log": ((nM, N, E), "A_log", "float32"),
        "D": ((nM, E), "ones", "float32"),
        "out_w": ((nM, E, D), "normal", dt),
    }


def _dt_bias_init(shape, dtype):
    from ..nn.initializer import Uniform
    dt = jnp.exp(Uniform(math.log(1e-3), math.log(1e-1))(shape, jnp.float32))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _a_log_init(shape, dtype):
    # S4D-real: A[n] = -(n + 1) for every channel
    n = jnp.arange(1, shape[-2] + 1, dtype=jnp.float32)[:, None]
    return jnp.broadcast_to(jnp.log(n), shape).astype(dtype)


class JambaForCausalLM(Layer):
    """``tensors``, where given, is a loader: ``tensors(name)`` hands back
    the parameter ``name`` of ``param_shapes(config)`` at its shape and
    dtype, and the constructor draws nothing."""

    def __init__(self, config: JambaConfig, tensors=None):
        t0_ns = time.perf_counter_ns()
        super().__init__()
        self.config = c = config
        from ..nn.initializer import Constant, Normal, Uniform
        from ..nn.functional.init_utils import param_attr_init
        from ..distributed.sharding_utils import annotate_param
        # the convolution as torch's Conv1d draws it (weight and bias
        # U(-k, k), k = (taps per channel)^-1/2); dt and A as Mamba's
        # initialisers give them
        bound = c.d_conv ** -0.5
        draw = {"normal": Normal(0.0, c.initializer_range),
                "ones": Constant(1.0), "conv": Uniform(-bound, bound),
                "A_log": _a_log_init, "dt_bias": _dt_bias_init}

        def given(name):
            def init(shape, dtype):
                x = tensors(name)
                if tuple(x.shape) != tuple(shape) or x.dtype != dtype:
                    raise ValueError(
                        f"{name}: the model holds {tuple(shape)} {dtype}, "
                        f"the loader gave {tuple(x.shape)} {x.dtype}")
                return x
            return init

        # the largest first, while nothing else is resident
        for name, (shape, how, dtype) in sorted(
                param_shapes(c).items(), key=lambda kv: -math.prod(kv[1][0])):
            p = param_attr_init(shape, jnp.dtype(dtype), None, False,
                                draw[how] if tensors is None else given(name))
            annotate_param(p, P())
            setattr(self, name, p)
        _trace.lifecycle_since("setup.model_init", t0_ns)

    # -- what the model caches -----------------------------------------------
    def cache_spec(self):
        """What a serving engine has to hold: for each of the ``kv_layers``
        attention layers one row ``[k ; v]`` of ``kv_row`` values per token
        (``kv_heads`` 0: no head axis; ``banded_walk``: the row is read by
        ``window_attention``'s walks over a full band), and one row per slot of each ``slot_state``
        array, ``(leading shape, shape per slot, dtype)`` with the slot
        axis between the two: each Mamba layer's state and the last
        ``d_conv - 1`` inputs of its convolution (in the shape
        ``selective_scan.tail_shape`` gives), both written by the
        selective scan alone."""
        c = self.config
        nA = c.layer_types.count(ATTN)
        nM = c.num_layers - nA
        return {
            "kv_layers": nA, "kv_heads": 0, "head_dim": 0,
            "kv_row": c.kv_row, "banded_walk": True,
            "slot_state": {
                "ssm_state": ((nM,), (c.d_state, c.inner), "float32"),
                "ssm_conv": ((nM,), _ss.tail_shape(c.d_conv - 1, c.inner),
                             c.dtype),
            },
        }

    def decode_state(self):
        """Raw device weights for the serving programs (one pytree the
        engine passes through jit unchanged)."""
        return {n: getattr(self, n)._data for n in param_shapes(self.config)}

    # -- the block, once -----------------------------------------------------
    def _layers(self, w, h, cache, attend, recur):
        """Every layer over ``h [B, T, D]``.  ``cache`` is whatever the
        two cache functions carry from layer to layer:

        * ``attend(cache, i, q, k, v) -> (o, cache)`` for attention layer
          ``i``: ``q [B, T, n_kv, G, hd]`` (scaled), ``k, v [B, T, n_kv,
          hd]``; ``o [B, T, H, hd]`` float32;
        * ``recur(cache, i, x, lw, inputs) -> (y, cache)`` for Mamba layer
          ``i``: ``x [B, T, E]`` is the input of the causal convolution
          (whose tail is cached), ``lw`` the layer's weights, ``inputs``
          turns the convolution's activated output into ``(dt, B, C)``;
          ``y [B, T, E]`` float32 is the scan's output with the skip.
        """
        c = self.config
        per = c.period
        n_periods = c.num_layers // per
        kinds = c.layer_types[:per]
        counts = {ATTN: kinds.count(ATTN), MAMBA: kinds.count(MAMBA)}

        def at(names, i):
            # one layer's slice of each stacked weight, read where it lies
            return {k: jax.lax.dynamic_index_in_dim(w[k], i, 0, False)
                    for k in names}

        def block(hh, cache, l, kind, i):
            lw = at(_COMMON, l)
            x = _rms(hh, lw["mixer_norm_w"], c.rms_norm_eps)
            if kind == ATTN:
                a, cache = self._attention(at(_ATTN, i), x, cache, i, attend)
            else:
                a, cache = self._mamba(at(_MAMBA, i), x, cache, i, recur)
            hh = hh + a
            g, u = jnp.split(_mm(_rms(hh, lw["mlp_norm_w"], c.rms_norm_eps),
                                 lw["gu_w"]), 2, axis=-1)
            return hh + _mm(jax.nn.silu(g) * u, lw["down_w"]), cache

        def body(carry, p):
            hh, cache = carry
            seen = {ATTN: 0, MAMBA: 0}
            for j, kind in enumerate(kinds):
                hh, cache = block(hh, cache, p * per + j, kind,
                                  p * counts[kind] + seen[kind])
                seen[kind] += 1
            return (hh, cache), None

        (h, cache), _ = jax.lax.scan(body, (h, cache),
                                     jnp.arange(n_periods, dtype=jnp.int32))
        for l in range(n_periods * per, c.num_layers):
            kind = c.layer_types[l]
            h, cache = block(h, cache, l, kind,
                             c.layer_types[:l].count(kind))
        return h, cache

    def _attention(self, kw, x, cache, i, attend):
        c = self.config
        B, T, _ = x.shape
        H, N, hd = c.num_heads, c.num_kv_heads, c.head_dim
        q, k, v = jnp.split(_mm(x, kw["qkv_w"]), [H * hd, (H + N) * hd],
                            axis=-1)
        q = (q.astype(jnp.float32) * hd ** -0.5).astype(x.dtype)
        o, cache = attend(cache, i, q.reshape(B, T, N, H // N, hd),
                          k.reshape(B, T, N, hd), v.reshape(B, T, N, hd))
        return _mm(o.reshape(B, T, H * hd).astype(x.dtype), kw["o_w"]), cache

    def _mamba(self, kw, x, cache, i, recur):
        c = self.config
        eps, R, N = c.rms_norm_eps, c.dt_rank, c.d_state
        xin, z = jnp.split(_mm(x, kw["in_w"]), 2, axis=-1)

        def inputs(xbar):
            """``(dt, B, C)`` float32 from the activated convolution."""
            d, b, cc = jnp.split(_mm32(xbar, kw["x_w"]), [R, R + N], axis=-1)
            dt = jax.nn.softplus(
                _mm32(_rms(d, kw["dt_norm_w"], eps), kw["dt_w"])
                + kw["dt_b"])
            return (dt, _rms(b, kw["b_norm_w"], eps).astype(jnp.float32),
                    _rms(cc, kw["c_norm_w"], eps).astype(jnp.float32))

        y, cache = recur(cache, i, xin, kw, inputs)
        y = y * jax.nn.silu(z.astype(jnp.float32))
        return _mm(y.astype(x.dtype), kw["out_w"]), cache

    @staticmethod
    def _convolve(kw, xin, tail, length=None):
        """The causal convolution with its bias and SiLU, in float32:
        ``(xbar [B, T, E], new tail)``."""
        f32 = jnp.float32
        y, tail = _gd.causal_conv(xin.astype(f32), kw["conv_w"].astype(f32),
                                  tail.astype(f32), length,
                                  bias=kw["conv_b"].astype(f32))
        return jax.nn.silu(y), tail

    @staticmethod
    def _scan(kw, xbar, dt, b, cc, state, conv, tail, rows, reset, run, i):
        """The scan over ``state`` and the rows' new convolution tails
        ``tail [R, W - 1, E]`` into ``conv``."""
        return _ss.scan(xbar, dt, b, cc, -jnp.exp(kw["A_log"]), kw["D"],
                        state, conv, tail.reshape((-1,) + conv.shape[2:]), i,
                        rows, reset, run)

    def _tails(self, conv):
        """``conv [..., a, b]`` as the last inputs ``[..., W - 1, E]``."""
        c = self.config
        return conv.reshape(conv.shape[:-2] + (c.d_conv - 1, c.inner))

    def _logits(self, w, h):
        h = _rms(h, w["lnf_w"], self.config.rms_norm_eps)
        return jnp.matmul(h, w["wte"].T, precision=matmul_precision(),
                          preferred_element_type=jnp.float32)

    @staticmethod
    def _line(k, v, row):
        """A token's cached row ``[k ; v]`` from ``k, v [..., n_kv, hd]``,
        zeros up to the pool's ``row``."""
        line = jnp.concatenate([k.reshape(k.shape[:-2] + (-1,)),
                                v.reshape(v.shape[:-2] + (-1,))], -1)
        pad = [(0, 0)] * (line.ndim - 1) + [(0, row - line.shape[-1])]
        return jnp.pad(line, pad)

    # -- the plain forward pass ----------------------------------------------
    def forward(self, input_ids):
        """Logits ``[B, T, V]`` of whole sequences, no cache."""
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        return Tensor(self.forward_logits(self.decode_state(),
                                          jnp.asarray(ids)))

    def forward_logits(self, w, ids):
        c = self.config
        B, T = ids.shape
        causal = jnp.tril(jnp.ones((T, T), bool))
        rows = jnp.arange(B)
        every = jnp.ones((B,), bool)

        def attend(cache, i, q, k, v):
            s = jnp.einsum("bqngd,bknd->bngqk", q, k,
                           preferred_element_type=jnp.float32)
            p = jax.nn.softmax(jnp.where(causal, s, NEG_INF), axis=-1)
            o = jnp.einsum("bngqk,bknd->bqngd", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
            return o.reshape(B, T, c.num_heads, c.head_dim), cache

        def recur(cache, i, xin, kw, inputs):
            tail = jnp.zeros((B, c.d_conv - 1, c.inner), jnp.float32)
            xbar, _ = self._convolve(kw, xin, tail)
            state = jnp.zeros((1, B, c.d_state, c.inner), jnp.float32)
            conv = jnp.zeros((1, B) + _ss.tail_shape(c.d_conv - 1, c.inner),
                             jnp.float32)
            y, _, _ = self._scan(kw, xbar, *inputs(xbar), state, conv, tail,
                                 rows, every, every, 0)
            return y, cache

        h, _ = self._layers(w, jnp.take(w["wte"], ids, axis=0), (), attend,
                            recur)
        return self._logits(w, h)

    # -- serving entry points (paddle_tpu.serving.LLMEngine, paged) ----------
    def prefill_paged(self, w, ids, start, length, bt, pool, pool_v, state,
                      slot, kernel=None):
        """One chunked-prefill step: ``ids [1, C]`` holds ``length`` tokens
        of one request at positions ``[start, start + length)``; ``bt`` is
        its block table, ``pool`` the attention layers' rows ``[kv_layers,
        n_blocks, bs, kv_row]`` (``pool_v`` is the engine's second pool,
        None for this family, handed back as it came), ``state`` the
        engine's per-slot arrays (see :meth:`cache_spec`), ``slot`` the
        request's row in them.  The row's state is taken as zero when
        ``start == 0`` (a slot's last owner leaves nothing behind),
        advanced over the ``length`` live positions only (``dt = 0`` past
        them), and written back in place.  The chunk's K/V rows are written
        first; its queries then attend over every live row before them
        (``kernels.window_attention`` with a full band: ``kernel="pallas"``
        is the Pallas kernel, otherwise the XLA twin).  Returns ``(pool,
        pool_v, state, logits [1, V])`` read at the chunk's last live
        token."""
        c = self.config
        C = ids.shape[1]
        bs, row = pool.shape[2], pool.shape[3]
        valid = jnp.arange(C) < length
        tokpos = start + jnp.arange(C)
        blk = jnp.where(valid, bt[tokpos // bs], 0)   # padding: trash block
        off = tokpos % bs
        fresh = jnp.reshape(start == 0, (1,))
        slots = jnp.reshape(slot, (1,)).astype(jnp.int32)
        if kernel not in (None, "off", "pallas"):
            raise ValueError(f"kernel={kernel!r}")
        fold = (_wa.window_prefill_attn if kernel == "pallas"
                else _wa.window_prefill_attn_xla)

        def attend(cache, i, q, k, v):
            p, st, tails = cache
            line = jnp.where(valid[:, None], self._line(k[0], v[0], row), 0)
            p = p.at[i, blk, off].set(line.astype(p.dtype))
            qg = jnp.moveaxis(q[0], 0, 2).astype(p.dtype)   # [n_kv, G, C, hd]
            o = fold(qg, p, i, bt, start, length, None)
            return (jnp.moveaxis(o, 2, 0).reshape(1, C, c.num_heads,
                                                  c.head_dim),
                    (p, st, tails))

        def recur(cache, i, xin, kw, inputs):
            p, st, tails = cache
            mine = jax.lax.dynamic_slice(tails, (i, slot, 0, 0),
                                         (1, 1) + tails.shape[2:])[0]
            tail = jnp.where(fresh, jnp.zeros((), tails.dtype),
                             self._tails(mine))
            xbar, tail = self._convolve(kw, xin, tail, length)
            dt, b, cc = inputs(xbar)
            y, st, tails = self._scan(
                kw, xbar, jnp.where(valid[None, :, None], dt, 0.0), b, cc,
                st, tails, tail, slots, fresh, jnp.ones((1,), bool), i)
            return y, (p, st, tails)

        h, (pool, st, tails) = self._layers(
            w, jnp.take(w["wte"], ids, axis=0),
            (pool, state["ssm_state"], state["ssm_conv"]), attend, recur)
        h_last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
        return (pool, pool_v, {"ssm_state": st, "ssm_conv": tails},
                self._logits(w, h_last[:, 0]))

    def decode_paged(self, w, tok, pos, bt, pool, pool_v, state, running,
                     kernel=None):
        """One decode step for ``B`` slot rows: ``tok``/``pos [B]``, ``bt
        [B, max_blocks]``, the pools and ``state`` as in
        :meth:`prefill_paged`, ``running [B]`` bool.  Each attention layer
        writes the token's row and walks every earlier one
        (``kernel="pallas"``: the Pallas walk, otherwise the XLA gather
        twin); each Mamba layer advances every running row's state by one
        token through one selective scan over the rows.  A row that is not
        running (free, or between two prefill chunks) is tabled to the
        trash block by the engine and keeps its ``state`` rows bit for bit.
        Returns ``(logits [B, V], pool, pool_v, state)``."""
        c = self.config
        B = tok.shape[0]
        bs, row = pool.shape[2], pool.shape[3]
        rows = jnp.arange(B)
        blk = bt[rows, pos // bs]
        lo = _wa.band(pos, None)
        if kernel not in (None, "off", "pallas"):
            raise ValueError(f"kernel={kernel!r}")
        walk = (_wa.window_decode_attn if kernel == "pallas"
                else _wa.window_decode_attn_xla)

        def attend(cache, i, q, k, v):
            p, st, tails = cache
            line = self._line(k[:, 0], v[:, 0], row).astype(p.dtype)
            p = p.at[i, blk, pos % bs].set(line)
            o = walk(q[:, 0], p, i, bt, pos, lo, c.num_kv_heads)
            return (o.reshape(B, 1, c.num_heads, c.head_dim),
                    (p, st, tails))

        def recur(cache, i, xin, kw, inputs):
            p, st, tails = cache
            xbar, tail = self._convolve(kw, xin, self._tails(tails[i]))
            y, st, tails = self._scan(kw, xbar, *inputs(xbar), st, tails,
                                      tail, rows, jnp.zeros((B,), bool),
                                      running, i)
            return y, (p, st, tails)

        h, (pool, st, tails) = self._layers(
            w, jnp.take(w["wte"], tok, axis=0)[:, None, :],
            (pool, state["ssm_state"], state["ssm_conv"]), attend, recur)
        return (self._logits(w, h[:, 0]), pool, pool_v,
                {"ssm_state": st, "ssm_conv": tails})
