"""Trinity (``model_type: afmoe``): window layers beside full ones, a shared
expert beside routed experts chosen by sigmoid scores with a bias.

The published configuration (Arcee Trinity Large; the ``config.json`` keys
under their own names in :meth:`TrinityConfig.from_hf`) is an RMSNorm
decoder with FOUR norms a layer, a sandwich: ``h = x + N_pa(Attn(N_in(x)))``
and ``x' = h + N_pm(FFN(N_pre(h)))``; the embedding is scaled by
``sqrt(hidden_size)`` (``mup_enabled``); a final norm and an untied head.

* Attention: grouped-query heads (query head ``h`` reads K/V head ``h //
  G``), an RMSNorm over each query's and key's head dims (one gain of
  ``head_dim`` each), and a gate: the heads' output times ``sigmoid(W_g
  u)`` before ``W_o``.  ``layer_types`` makes a layer a WINDOW layer
  (rotary positions, each head's dims as two halves; a query at ``i`` sees
  ``i - W < j <= i``) or a FULL one (no positional encoding, causal).
* FFN: the first ``num_dense_layers`` layers a SwiGLU of
  ``intermediate_size``; every later layer ``num_shared_experts`` shared
  experts (one SwiGLU of their joint width) plus the routed experts:
  ``sigma = sigmoid(W_r z)`` in float32, the top ``k`` of ``sigma + b``
  chosen (``b`` a learned per-expert bias), weighed by ``sigma``
  renormalised over the chosen, times ``route_scale``
  (``kernels/moe.biased_sigmoid_top_k``).

A model may hold a share of the routed experts (``experts_held = (first,
count)``): the router runs over all ``num_experts``, the sum over the
chosen experts that are held.

:meth:`TrinityForCausalLM.cache_spec` tells the serving engine what it
caches: one row ``[k ; v]`` a token for the full layers (``kv_layers``,
paged as usual), and under ``window`` that the window layers keep only the
last ``size`` positions: the engine holds them in a second pool whose
blocks a row reuses as a ring (``serving/paged.py``).  Both pools are read
by one walk, ``kernels/window_attention``.

The block is written once (:meth:`_layers`), as in ``deepseek_v2.py``: the
three entry points differ only in the function that reaches the cache.
The leading dense layers run one by one, the expert layers as a scan over
whole periods of ``layer_types`` (and one by one for a partial period).
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
from ..kernels import moe as _moe
from ..kernels import window_attention as _wa
from ..kernels._shapes import NEG_INF
from ..kernels.rms_norm import rms_norm_reference as _rms
from ..nn.layer.layers import Layer
from ..profiler import host_tracer as _trace

SLIDING, FULL = "sliding_attention", "full_attention"


class TrinityConfig:
    """The sizes of an ``afmoe`` model under this package's names
    (``from_hf`` takes the published ``config.json`` keys)."""

    def __init__(self, vocab_size=200192, hidden_size=3072,
                 intermediate_size=12288, moe_intermediate_size=3072,
                 num_layers=60, num_dense_layers=6, num_heads=48,
                 num_kv_heads=8, head_dim=128, layer_types=None,
                 global_attn_every_n_layers=4, sliding_window=4096,
                 num_experts=256, experts_held=None, num_experts_per_tok=4,
                 num_shared_experts=1, route_scale=2.448, rms_norm_eps=1e-5,
                 rope_theta=10000.0, mup_enabled=True, max_seq_len=262144,
                 initializer_range=0.02, dtype="float32"):
        n = int(global_attn_every_n_layers)
        layer_types = list(layer_types or [
            FULL if (i + 1) % n == 0 else SLIDING for i in range(num_layers)])
        first, count = experts_held or (0, num_experts)
        if len(layer_types) != num_layers or set(layer_types) - {SLIDING,
                                                                 FULL}:
            raise ValueError("layer_types must name each layer sliding or "
                             "full")
        if not 0 <= num_dense_layers < num_layers:
            raise ValueError("an afmoe model has dense layers first and at "
                             "least one expert layer after them")
        if num_heads % num_kv_heads or head_dim % 2:
            raise ValueError("query heads must group over the K/V heads and "
                             "head_dim be even")
        if not (0 <= first and count >= 1 and first + count <= num_experts
                and 1 <= num_experts_per_tok <= num_experts):
            raise ValueError(f"experts_held {(first, count)} is not a range "
                             f"of the {num_experts} experts")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_layers = num_layers
        self.num_dense_layers = num_dense_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.layer_types = layer_types
        self.period = n
        self.sliding_window = int(sliding_window)
        self.num_experts = num_experts
        self.experts_held = (int(first), int(count))
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.route_scale = float(route_scale)
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.mup_enabled = bool(mup_enabled)
        self.max_seq_len = max_seq_len
        self.initializer_range = initializer_range
        self.dtype = dtype
        # what the serving engine asks of any model's config
        self.use_rope = True

    @classmethod
    def from_hf(cls, hf, experts_held=None, num_experts=None, **kw):
        """From the keys of the published ``config.json``.  A file cut to
        one chip's share states the experts held under ``num_experts``:
        pass the published count and the share, ``experts_held=(first,
        count)``."""
        if hf.get("score_func", "sigmoid") != "sigmoid":
            raise ValueError(f"score_func {hf.get('score_func')!r}: only "
                             "sigmoid scores with a bias are implemented")
        if max(hf.get(k, 1) for k in ("n_group", "topk_group",
                                      "num_expert_groups",
                                      "num_limited_groups")) > 1:
            raise ValueError("group-limited routing (n_group > 1) is not "
                             "implemented for afmoe")
        if hf.get("rope_scaling"):
            raise ValueError("rope_scaling is not implemented for afmoe")
        if (not hf.get("route_norm", True) or hf.get("tie_word_embeddings")
                or hf.get("hidden_act", "silu") != "silu"):
            raise ValueError("only afmoe's own block is implemented (the "
                             "routed weights renormalised, silu, an untied "
                             "head)")
        return cls(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_dense_layers=hf["num_dense_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
            layer_types=hf.get("layer_types"),
            global_attn_every_n_layers=hf["global_attn_every_n_layers"],
            sliding_window=hf["sliding_window"],
            num_experts=num_experts or hf["num_experts"],
            experts_held=experts_held,
            num_experts_per_tok=hf["num_experts_per_tok"],
            num_shared_experts=hf["num_shared_experts"],
            route_scale=hf["route_scale"], rms_norm_eps=hf["rms_norm_eps"],
            rope_theta=hf["rope_theta"],
            mup_enabled=hf.get("mup_enabled", False),
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

    def pool_index(self, i):
        """Layer ``i``'s index among the layers of its kind (the layer of
        its pool)."""
        return self.layer_types[:i].count(self.layer_types[i])


#: stacked over all layers / the dense layers / the expert layers
_ATTN = ("attn_in_w", "attn_post_w", "ffn_pre_w", "ffn_post_w", "qkvg_w",
         "q_norm_w", "k_norm_w", "o_w")
_DENSE = ("mlp_gu_w", "mlp_down_w")
_MOE = ("router_w", "expert_bias", "shared_gu_w", "shared_down_w")
# ("expert_gu_w", "expert_down_w" are applied where they lie, all layers'
# experts as the groups of one product: kernels/moe.py)


def _mm(x, w):
    return jnp.matmul(x, w, precision=matmul_precision())


def param_shapes(c):
    """``{parameter: (shape, how it is drawn, dtype)}`` of a model of
    configuration ``c``: the constructor's table (and what a compile for a
    described chip builds its shapes from).  ``qkvg_w`` is ``[W_q | W_k |
    W_v | W_g]`` (one product a layer), a ``*_gu_w`` the gate beside the up
    projection; ``expert_bias`` is float32 (it only chooses)."""
    D, V, L = c.hidden_size, c.vocab_size, c.num_layers
    H, N, hd = c.num_heads, c.num_kv_heads, c.head_dim
    nD = c.num_dense_layers
    nM = L - nD
    F, Fm = c.intermediate_size, c.moe_intermediate_size
    Fs = c.num_shared_experts * Fm
    E = c.experts_held[1]
    dt = c.dtype
    return {
        "wte": ((V, D), "normal", dt), "lnf_w": ((D,), "ones", dt),
        "lm_head": ((D, V), "normal", dt),
        "attn_in_w": ((L, D), "ones", dt),
        "attn_post_w": ((L, D), "ones", dt),
        "ffn_pre_w": ((L, D), "ones", dt),
        "ffn_post_w": ((L, D), "ones", dt),
        "qkvg_w": ((L, D, 2 * H * hd + 2 * N * hd), "normal", dt),
        "q_norm_w": ((L, hd), "ones", dt),
        "k_norm_w": ((L, hd), "ones", dt),
        "o_w": ((L, H * hd, D), "normal", dt),
        "mlp_gu_w": ((nD, D, 2 * F), "normal", dt),
        "mlp_down_w": ((nD, F, D), "normal", dt),
        "router_w": ((nM, D, c.num_experts), "normal", dt),
        "expert_bias": ((nM, c.num_experts), "zeros", "float32"),
        "shared_gu_w": ((nM, D, 2 * Fs), "normal", dt),
        "shared_down_w": ((nM, Fs, D), "normal", dt),
        "expert_gu_w": ((nM, E, D, 2 * Fm), "normal", dt),
        "expert_down_w": ((nM, E, Fm, D), "normal", dt),
    }


@functools.partial(jax.jit, donate_argnums=0)
def _put_layer(stacked, layer, i):
    return jax.lax.dynamic_update_index_in_dim(stacked, layer, i, 0)


def _swiglu(x, gu_w, down_w):
    g, u = jnp.split(_mm(x, gu_w), 2, axis=-1)
    return _mm(jax.nn.silu(g) * u, down_w)


class TrinityForCausalLM(Layer):
    """``tensors``, where given, is a loader: ``tensors(name)`` hands back
    the parameter ``name`` of ``param_shapes(config)`` at its shape and
    dtype, and the constructor draws nothing."""

    def __init__(self, config: TrinityConfig, tensors=None):
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
            # eager draw takes about four times its float32 size
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

        draw = {"normal": by_layer, "ones": Constant(1.0),
                "zeros": Constant(0.0)}
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
        """What a serving engine has to hold: for each of the ``kv_layers``
        full layers one row ``[k ; v]`` of ``kv_row`` values per token
        (``kv_heads`` 0: no head axis); under ``window`` the same rows for
        the ``layers`` window layers, of which a query needs the last
        ``size`` positions only; no per-slot state; and the ``step_state``
        arrays ``(shape, dtype)`` every serving program takes and hands on
        (the expert layers' load counts)."""
        c = self.config
        nF = c.layer_types.count(FULL)
        return {
            "kv_layers": nF, "kv_heads": 0, "head_dim": 0,
            "kv_row": c.kv_row, "slot_state": {},
            "window": {"size": c.sliding_window,
                       "layers": c.num_layers - nF},
            "step_state": {
                "moe_assignments": ((c.num_layers - c.num_dense_layers,
                                     c.experts_held[1]), "int32"),
                "moe_tokens": ((), "int32"),
            },
        }

    def moe_load(self, state):
        """The expert layers' load so far from an engine's ``step_state()``
        reading, as ``DeepseekV2ForCausalLM.moe_load`` gives it, and its
        ``serving.moe.*`` records."""
        return _moe.publish_load(state, self._moe_seen)

    def decode_state(self):
        """Raw device weights for the serving programs (one pytree the
        engine passes through jit unchanged)."""
        return {n: getattr(self, n)._data for n in param_shapes(self.config)}

    # -- the block, once -----------------------------------------------------
    def _rope(self, x, pos):
        """``x [B, T, heads, hd]`` turned by its positions ``pos [B, T]``,
        each head's dims as two halves; float32 arithmetic, ``x``'s dtype
        back."""
        ang = pos[..., None, None].astype(jnp.float32) * self.config.inv_freq
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1).astype(x.dtype)

    def _embed(self, w, ids):
        h = jnp.take(w["wte"], ids, axis=0)
        if self.config.mup_enabled:
            h = (h.astype(jnp.float32)
                 * math.sqrt(self.config.hidden_size)).astype(h.dtype)
        return h

    def _layers(self, w, h, pos, cache, attend, live=None):
        """Every layer over ``h [B, T, D]`` at positions ``pos [B, T]``.
        ``cache`` is ``(what attend carries, assignments, tokens)``;
        ``attend(carried, i, kind, q, k, v) -> (o [B, T, H, hd] float32,
        carried)`` for the ``i``-th layer of ``kind`` (its layer in that
        kind's pool): ``q [B, T, H, hd]`` normalised, turned where the
        kind turns and scaled, ``k`` (normalised, turned likewise) and ``v
        [B, T, n_kv, hd]``.  ``live [B, T]`` bool leaves tokens out of the
        routed experts and their counts."""
        c = self.config
        nD, P_ = c.num_dense_layers, c.period
        nM = c.num_layers - nD
        B, T, D = h.shape
        live = jnp.ones((B, T), bool) if live is None else live
        kinds = c.layer_types

        def at(names, i):
            # one layer's slice of each stacked weight, read where it lies
            return {k: jax.lax.dynamic_index_in_dim(w[k], i, 0, False)
                    for k in names}

        def block(hh, carried, counts, i, kind, k_index, j):
            # layer i (traced in a scan), of ``kind``, the k_index-th of its
            # kind; j its index among the expert layers, None if dense
            lw = at(_ATTN, i)
            a, carried = self._attention(lw, _rms(hh, lw["attn_in_w"],
                                                  c.rms_norm_eps),
                                         pos, carried, k_index, kind, attend)
            hh = hh + _rms(a, lw["attn_post_w"], c.rms_norm_eps)
            z = _rms(hh, lw["ffn_pre_w"], c.rms_norm_eps)
            if j is None:
                dw = at(_DENSE, i)
                f = _swiglu(z, dw["mlp_gu_w"], dw["mlp_down_w"])
            else:
                f, count = self._expert_ffn(w, j, z.reshape(B * T, D),
                                            live.reshape(B * T))
                f = f.reshape(B, T, D).astype(hh.dtype)
                counts = jax.lax.dynamic_update_index_in_dim(
                    counts, jax.lax.dynamic_index_in_dim(counts, j, 0, False)
                    + count, j, 0)
            return (hh + _rms(f, lw["ffn_post_w"], c.rms_norm_eps), carried,
                    counts)

        carried, counts, tokens = cache
        for i in range(nD):
            h, carried, counts = block(h, carried, counts, i, kinds[i],
                                       c.pool_index(i), None)
        period = kinds[nD:nD + P_]
        n_periods = nM // P_ if all(
            kinds[nD + p * P_:nD + (p + 1) * P_] == period
            for p in range(nM // P_)) else 0
        base = {kind: kinds[:nD].count(kind) for kind in (SLIDING, FULL)}
        per = {kind: period.count(kind) for kind in (SLIDING, FULL)}

        def body(carry, p):
            hh, carried, counts = carry
            seen = {SLIDING: 0, FULL: 0}
            for j, kind in enumerate(period):
                hh, carried, counts = block(
                    hh, carried, counts, nD + p * P_ + j, kind,
                    base[kind] + p * per[kind] + seen[kind], p * P_ + j)
                seen[kind] += 1
            return (hh, carried, counts), None

        if n_periods:
            (h, carried, counts), _ = jax.lax.scan(
                body, (h, carried, counts),
                jnp.arange(n_periods, dtype=jnp.int32))
        for i in range(nD + n_periods * P_, c.num_layers):
            h, carried, counts = block(h, carried, counts, i, kinds[i],
                                       c.pool_index(i), i - nD)
        return h, (carried, counts, tokens + live.sum(dtype=tokens.dtype))

    def _attention(self, lw, x, pos, carried, i, kind, attend):
        c = self.config
        B, T, _ = x.shape
        H, N, hd = c.num_heads, c.num_kv_heads, c.head_dim
        q, k, v, g = jnp.split(
            _mm(x, lw["qkvg_w"]), [H * hd, H * hd + N * hd,
                                   H * hd + 2 * N * hd], axis=-1)
        q = _rms(q.reshape(B, T, H, hd), lw["q_norm_w"], c.rms_norm_eps)
        k = _rms(k.reshape(B, T, N, hd), lw["k_norm_w"], c.rms_norm_eps)
        if kind == SLIDING:
            q, k = self._rope(q, pos), self._rope(k, pos)
        q = (q.astype(jnp.float32) * hd ** -0.5).astype(x.dtype)
        o, carried = attend(carried, i, kind, q, k, v.reshape(B, T, N, hd))
        o = o.reshape(B, T, H * hd) * jax.nn.sigmoid(g.astype(jnp.float32))
        return _mm(o.astype(x.dtype), lw["o_w"]), carried

    def _expert_ffn(self, w, j, z, live):
        """Expert layer ``j`` over ``z [N, D]``: the shared experts, and the
        routed experts held here for the rows that are ``live``.  Returns
        ``(f [N, D] float32, count [held] int32)``."""
        c = self.config
        lw = {k: jax.lax.dynamic_index_in_dim(w[k], j, 0, False)
              for k in _MOE}
        weight, expert = _moe.biased_sigmoid_top_k(
            jnp.matmul(z, lw["router_w"], preferred_element_type=jnp.float32,
                       precision=matmul_precision()),
            lw["expert_bias"], c.num_experts_per_tok, c.route_scale)
        routed, count = _moe.held_expert_ffn(
            z, weight, expert, w["expert_gu_w"], w["expert_down_w"],
            c.experts_held[0], j, live)
        return (_swiglu(z, lw["shared_gu_w"], lw["shared_down_w"])
                .astype(jnp.float32) + routed), count

    def _logits(self, w, h):
        h = _rms(h, w["lnf_w"], self.config.rms_norm_eps)
        return _mm(h, w["lm_head"]).astype(jnp.float32)

    def _no_counts(self):
        c = self.config
        return (jnp.zeros((c.num_layers - c.num_dense_layers,
                           c.experts_held[1]), jnp.int32),
                jnp.zeros((), jnp.int32))

    def _grouped(self, q):
        """``q [..., H, hd]`` -> ``[..., n_kv, G, hd]``."""
        c = self.config
        return q.reshape(q.shape[:-2] + (c.num_kv_heads, -1, c.head_dim))

    def _line(self, k, v, row):
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
        """Attention over the whole sequence under each kind's mask."""
        c = self.config
        B, T = ids.shape
        i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        masks = {FULL: j <= i, SLIDING: (j <= i) & (j > i - c.sliding_window)}

        def attend(carried, _i, kind, q, k, v):
            s = jnp.einsum("bqngd,bknd->bngqk", self._grouped(q), k,
                           preferred_element_type=jnp.float32)
            p = jax.nn.softmax(jnp.where(masks[kind], s, NEG_INF), axis=-1)
            o = jnp.einsum("bngqk,bknd->bqngd", p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
            return o.reshape(B, T, c.num_heads, c.head_dim), carried

        pos = jnp.broadcast_to(jnp.arange(T), (B, T))
        h, _ = self._layers(w, self._embed(w, ids), pos,
                            ((),) + self._no_counts(), attend)
        return self._logits(w, h)

    # -- serving entry points (paddle_tpu.serving.LLMEngine, paged) ----------
    def prefill_paged(self, w, ids, start, length, bt, pool, wpool, state,
                      slot, kernel=None, *, window_entries):
        """One chunked-prefill step: ``ids [1, C]`` holds ``length`` tokens
        of one request at positions ``[start, start + length)``; ``bt`` is
        its block table for the full layers' ``pool`` followed by its
        ``window_entries`` entries into ``wpool`` (the window layers'
        ring: logical block ``b`` at entry ``b mod window_entries``), both
        ``[layers, n_blocks, bs, row]``; ``state`` the engine's
        ``step_state`` arrays.  The chunk's rows are written first; its
        queries then attend over the live rows of their band
        (``kernels.window_attention``: a full layer from position 0, a
        window layer from ``W - 1`` before each query); ``kernel="pallas"``
        (the engine's choice, as for the decode walk) is the Pallas kernel,
        otherwise the XLA twin.  Returns ``(pool, wpool, state, logits [1,
        V])`` read at the chunk's last live token."""
        c = self.config
        C = ids.shape[1]
        W = c.sliding_window
        n_w = window_entries
        bs, row = pool.shape[2], pool.shape[3]
        tables = {FULL: bt[:-n_w], SLIDING: bt[-n_w:]}
        valid = jnp.arange(C) < length
        tokpos = start + jnp.arange(C)
        lblk = tokpos // bs
        off = tokpos % bs
        where = {FULL: jnp.where(valid, tables[FULL][lblk], 0),
                 SLIDING: jnp.where(valid, tables[SLIDING][lblk % n_w], 0)}
        window = {FULL: None, SLIDING: W}
        if kernel not in (None, "off", "pallas"):
            raise ValueError(f"kernel={kernel!r}")
        fold = (_wa.window_prefill_attn if kernel == "pallas"
                else _wa.window_prefill_attn_xla)

        def attend(carried, i, kind, q, k, v):
            pools = dict(zip((FULL, SLIDING), carried))
            p = pools[kind]
            line = jnp.where(valid[:, None], self._line(k[0], v[0], row), 0)
            p = p.at[i, where[kind], off].set(line.astype(p.dtype))
            qg = jnp.moveaxis(self._grouped(q[0]), 0, 2).astype(p.dtype)
            o = fold(qg, p, i, tables[kind], start, length, window[kind])
            pools[kind] = p
            return (jnp.moveaxis(o, 2, 0).reshape(1, C, c.num_heads,
                                                  c.head_dim),
                    (pools[FULL], pools[SLIDING]))

        h, ((pool, wpool), counts, tokens) = self._layers(
            w, self._embed(w, ids), tokpos[None],
            ((pool, wpool), state["moe_assignments"], state["moe_tokens"]),
            attend, valid[None])
        h_last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
        return (pool, wpool,
                {"moe_assignments": counts, "moe_tokens": tokens},
                self._logits(w, h_last[:, 0]))

    def decode_paged(self, w, tok, pos, bt, pool, wpool, state, running,
                     kernel=None, *, window_entries):
        """One decode step for ``B`` slot rows: ``tok``/``pos [B]``, ``bt
        [B, max_blocks + window_entries]`` (each row's full table, then its
        window ring), the pools and ``state`` as in :meth:`prefill_paged`,
        ``running [B]`` bool.  Each layer writes the token's row and then
        walks its band (``kernels.window_attention``: a window layer from
        ``max(0, pos - W + 1)``, a full layer from 0); ``kernel="pallas"``
        is the Pallas walk, otherwise the XLA gather twin.  A row that is
        not running is tabled to the trash block by the engine and is left
        out of the routed experts.  Returns ``(logits [B, V], pool, wpool,
        state)``."""
        c = self.config
        B = tok.shape[0]
        N = c.num_kv_heads
        n_w = window_entries
        bs, row = pool.shape[2], pool.shape[3]
        tables = {FULL: bt[:, :-n_w], SLIDING: bt[:, -n_w:]}
        rows = jnp.arange(B)
        where = {FULL: tables[FULL][rows, pos // bs],
                 SLIDING: tables[SLIDING][rows, (pos // bs) % n_w]}
        lo = {FULL: _wa.band(pos, None), SLIDING: _wa.band(
            pos, c.sliding_window)}
        if kernel not in (None, "off", "pallas"):
            raise ValueError(f"kernel={kernel!r}")
        walk = (_wa.window_decode_attn if kernel == "pallas"
                else _wa.window_decode_attn_xla)

        def attend(carried, i, kind, q, k, v):
            pools = dict(zip((FULL, SLIDING), carried))
            p = pools[kind]
            line = self._line(k[:, 0], v[:, 0], row).astype(p.dtype)
            p = p.at[i, where[kind], pos % bs].set(line)
            o = walk(self._grouped(q[:, 0]), p, i, tables[kind], pos,
                     lo[kind], N)
            pools[kind] = p
            return (o.reshape(B, 1, c.num_heads, c.head_dim),
                    (pools[FULL], pools[SLIDING]))

        h, ((pool, wpool), counts, tokens) = self._layers(
            w, self._embed(w, tok[:, None]), pos[:, None],
            ((pool, wpool), state["moe_assignments"], state["moe_tokens"]),
            attend, running[:, None])
        return (self._logits(w, h[:, 0]), pool, wpool,
                {"moe_assignments": counts, "moe_tokens": tokens})
