"""DeepSeek-V2: latent attention (MLA) and routed beside shared experts.

The published configuration (``model_type: deepseek_v2``, arXiv:2405.04434)
is a pre-norm RMSNorm decoder with rotary positions stretched by YaRN.
Attention is multi-head latent attention: queries go through a low-rank
pair ``W_DQ``/``W_UQ`` (with ``W_QR`` for their rotary part), keys and
values are rebuilt from ONE compressed row per token, ``c_kv = RMSNorm(
W_DKV x)`` beside a rotary key ``k_pe = RoPE(W_KR x)`` that all heads
share, and only that row, ``[c_kv ; k_pe]``, is cached.  The first
``first_k_dense_replace`` layers have a dense SwiGLU MLP; every later
layer has ``n_shared_experts`` shared experts (one SwiGLU of their joint
width) and routed experts chosen by group-limited top-k
(``kernels/moe.py``).

Attention comes in two forms of the same mathematics.  The materialised
form up-projects cached rows to per-head keys and values (``W_UK``,
``W_UV``) and attends as usual: the plain forward pass and the chunked
prefill, which rebuilds the prefix in tiles.  The absorbed form folds
``W_UK`` into the query and ``W_UV`` into the output and attends over the
cached rows themselves (``kernels/mla_attention.py``): the decode step.

A model may hold a share of the routed experts (``experts_held = (first,
count)``): the router, the groups and the top-k run over all
``n_routed_experts``, the sum over the chosen experts that are held.
With the whole range held that is the published layer.

:meth:`DeepseekV2ForCausalLM.cache_spec` tells the serving engine what it
caches: one row of ``kv_lora_rank + qk_rope_head_dim`` values per token and
layer, no head axis, and two counters of the expert layers' load that the
programs carry forward on the device.

The block is written once (:meth:`_layers`), as in ``olmo_hybrid.py``: the
three entry points differ only in the function that reaches the cache.
Weights are stacked per kind of layer and indexed where they lie.
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
from ..kernels import mla_attention as _mla
from ..kernels import moe as _moe
from ..kernels._shapes import NEG_INF
from ..kernels.rms_norm import rms_norm_reference as _rms
from ..nn.layer.layers import Layer
from ..profiler import host_tracer as _trace

#: cached positions a prefill chunk up-projects and attends to per step
#: of its walk over the live rows (a whole number of blocks)
_KEY_TILE = 2048


def yarn_inv_freq(dim, theta, factor, original_max, beta_fast, beta_slow):
    """The ``dim // 2`` rotary frequencies under YaRN (arXiv:2309.00071):
    the model's own where a dimension turns more than ``beta_fast`` times
    over the original length, those divided by ``factor`` where it turns
    less than ``beta_slow`` times, a linear ramp between."""
    def turns_at(rot):
        return dim * math.log(original_max / (rot * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    extra = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / ((high if high != low else high + 0.001) - low), 0, 1)
    return extra / factor * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


class DeepseekV2Config:
    """The sizes of a ``deepseek_v2`` model under this package's names
    (``from_hf`` takes the published ``config.json`` keys)."""

    def __init__(self, vocab_size=102400, hidden_size=5120,
                 intermediate_size=12288, moe_intermediate_size=1536,
                 num_layers=60, num_heads=128, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, n_shared_experts=2,
                 n_routed_experts=160, experts_held=None, n_group=8,
                 topk_group=3, num_experts_per_tok=6,
                 routed_scaling_factor=16.0, first_k_dense_replace=1,
                 rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
                 max_seq_len=163840, initializer_range=0.02,
                 dtype="float32"):
        first, count = experts_held or (0, n_routed_experts)
        if not (0 <= first and count >= 1
                and first + count <= n_routed_experts):
            raise ValueError(f"experts_held {(first, count)} is not a "
                             f"range of the {n_routed_experts} experts")
        if n_routed_experts % n_group or not (
                1 <= topk_group <= n_group
                and num_experts_per_tok
                <= topk_group * (n_routed_experts // n_group)):
            raise ValueError("the routing groups do not fit the experts")
        if not 0 <= first_k_dense_replace < num_layers:
            raise ValueError("a deepseek_v2 model has dense layers first "
                             "and at least one expert layer after them")
        if qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.n_shared_experts = n_shared_experts
        self.n_routed_experts = n_routed_experts
        self.experts_held = (int(first), int(count))
        self.n_group = n_group
        self.topk_group = topk_group
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.first_k_dense_replace = first_k_dense_replace
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling or {})
        self.max_seq_len = max_seq_len
        self.initializer_range = initializer_range
        self.dtype = dtype
        # what the serving engine asks of any model's config
        self.use_rope = True

    @classmethod
    def from_hf(cls, hf, experts_held=None, n_routed_experts=None, **kw):
        """From the keys of the published ``config.json``.  A file cut to
        one chip's share states the experts held under
        ``n_routed_experts``: pass the published count and the share,
        ``experts_held=(first, count)``."""
        if hf.get("num_key_value_heads",
                  hf["num_attention_heads"]) != hf["num_attention_heads"]:
            raise ValueError("latent attention has no grouped K/V heads")
        if (hf.get("topk_method", "group_limited_greedy")
                != "group_limited_greedy"
                or hf.get("scoring_func", "softmax") != "softmax"
                or hf.get("norm_topk_prob", False)
                or hf.get("moe_layer_freq", 1) != 1):
            raise ValueError("only DeepSeek-V2's own routing is implemented "
                             "(group_limited_greedy over a softmax, not "
                             "renormalised, every later layer an expert "
                             "layer)")
        scaling = hf.get("rope_scaling")
        if scaling is not None and scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling type {scaling.get('type')!r}")
        return cls(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            n_shared_experts=hf["n_shared_experts"],
            n_routed_experts=n_routed_experts or hf["n_routed_experts"],
            experts_held=experts_held, n_group=hf["n_group"],
            topk_group=hf["topk_group"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            routed_scaling_factor=hf["routed_scaling_factor"],
            first_k_dense_replace=hf["first_k_dense_replace"],
            rms_norm_eps=hf["rms_norm_eps"], rope_theta=hf["rope_theta"],
            rope_scaling=scaling,
            max_seq_len=hf["max_position_embeddings"], **kw)

    @property
    def latent_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def inv_freq(self):
        rs = self.rope_scaling
        if not rs:
            return yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                                 1.0, 1, 1, 1)
        return yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, rs["factor"],
            rs["original_max_position_embeddings"], rs["beta_fast"],
            rs["beta_slow"])

    @property
    def rope_mscale(self):
        """What cos and sin are multiplied by."""
        rs = self.rope_scaling
        if not rs:
            return 1.0
        return (yarn_mscale(rs["factor"], rs.get("mscale", 1))
                / yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0)))

    @property
    def softmax_scale(self):
        rs = self.rope_scaling
        m = yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0)) \
            if rs else 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * m * m


#: stacked over all layers / the dense layers / the expert layers
_ATTN = ("attn_norm_w", "ffn_norm_w", "q_a_w", "q_a_norm_w", "q_b_w",
         "kv_a_w", "kv_a_norm_w", "kv_b_k_w", "kv_b_v_w", "o_w")
_DENSE = ("mlp_gu_w", "mlp_down_w")
_MOE = ("router_w", "shared_gu_w", "shared_down_w")
# ("expert_gu_w", "expert_down_w" are applied where they lie, all layers'
# experts as the groups of one product: kernels/moe.py)


def _mm(x, w):
    return jnp.matmul(x, w, precision=matmul_precision())


def param_shapes(c):
    """``{parameter: (shape, how it is drawn, dtype)}`` of a model of
    configuration ``c``: the constructor's table (and what a compile for
    a described chip builds its shapes from).  ``q_b_w`` is ``[W_UQ |
    W_QR]`` (all heads' 128 then all heads' 64), ``kv_a_w`` ``[W_DKV |
    W_KR]``, a ``*_gu_w`` the gate beside the up projection."""
    D, V, L = c.hidden_size, c.vocab_size, c.num_layers
    H, R, Rq = c.num_heads, c.kv_lora_rank, c.q_lora_rank
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    nD = c.first_k_dense_replace
    nM = L - nD
    F, Fm = c.intermediate_size, c.moe_intermediate_size
    Fs = c.n_shared_experts * Fm
    E = c.experts_held[1]
    dt = c.dtype
    return {
        "wte": ((V, D), "normal", dt), "lnf_w": ((D,), "ones", dt),
        "lm_head": ((D, V), "normal", dt),
        "attn_norm_w": ((L, D), "ones", dt),
        "ffn_norm_w": ((L, D), "ones", dt),
        "q_a_w": ((L, D, Rq), "normal", dt),
        "q_a_norm_w": ((L, Rq), "ones", dt),
        "q_b_w": ((L, Rq, H * (dn + dr)), "normal", dt),
        "kv_a_w": ((L, D, R + dr), "normal", dt),
        "kv_a_norm_w": ((L, R), "ones", dt),
        "kv_b_k_w": ((L, R, H * dn), "normal", dt),
        "kv_b_v_w": ((L, R, H * dv), "normal", dt),
        "o_w": ((L, H * dv, D), "normal", dt),
        "mlp_gu_w": ((nD, D, 2 * F), "normal", dt),
        "mlp_down_w": ((nD, F, D), "normal", dt),
        "router_w": ((nM, D, c.n_routed_experts), "normal", dt),
        "shared_gu_w": ((nM, D, 2 * Fs), "normal", dt),
        "shared_down_w": ((nM, Fs, D), "normal", dt),
        "expert_gu_w": ((nM, E, D, 2 * Fm), "normal", dt),
        "expert_down_w": ((nM, E, Fm, D), "normal", dt),
    }


def _kernel_mode(kernel):
    """The serving engine's ``kv_kernel`` as the two entry points take it:
    ``"pallas"`` for the Pallas kernels, ``None`` or ``"off"`` for their
    XLA twins."""
    mode = kernel or "off"
    if mode not in ("off", "pallas"):
        raise ValueError(f"kernel={mode!r}")
    return mode


@functools.partial(jax.jit, donate_argnums=0)
def _put_layer(stacked, layer, i):
    return jax.lax.dynamic_update_index_in_dim(stacked, layer, i, 0)


def _swiglu(x, gu_w, down_w):
    g, u = jnp.split(_mm(x, gu_w), 2, axis=-1)
    return _mm(jax.nn.silu(g) * u, down_w)


class DeepseekV2ForCausalLM(Layer):
    def __init__(self, config: DeepseekV2Config):
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
            # eager draw takes about four times its float32 size, and the
            # 40 experts held of four layers are 5 GB in bfloat16
            if len(shape) < 3:
                return normal(shape, dtype)
            out = jnp.zeros(shape, dtype)
            for i in range(shape[0]):
                out = _put_layer(out, by_layer(shape[1:], dtype), i)
            return out

        draw = {"normal": by_layer, "ones": Constant(1.0)}
        # the largest first, while nothing else is resident
        for name, (shape, how, dtype) in sorted(
                param_shapes(c).items(), key=lambda kv: -math.prod(kv[1][0])):
            p = param_attr_init(shape, jnp.dtype(dtype), None, False,
                                draw[how])
            annotate_param(p, P())
            setattr(self, name, p)
        # what moe_load() has published so far
        self._moe_seen = {"assignments": 0, "tokens": 0}
        _trace.lifecycle_since("setup.model_init", t0_ns)

    # -- what the model caches -----------------------------------------------
    def cache_spec(self):
        """What a serving engine has to hold: for each of ``kv_layers``
        layers one row of ``kv_row`` values per token, with no head axis
        (``kv_heads`` 0), no per-slot state, and the ``step_state`` arrays
        ``(shape, dtype)`` that every serving program takes and hands on:
        the live (token, expert) pairs each held expert of each expert
        layer has taken, and the live tokens routed."""
        c = self.config
        nM = c.num_layers - c.first_k_dense_replace
        return {
            "kv_layers": c.num_layers, "kv_heads": 0, "head_dim": 0,
            "kv_row": c.latent_width, "slot_state": {},
            "step_state": {
                "moe_assignments": ((nM, c.experts_held[1]), "int32"),
                "moe_tokens": ((), "int32"),
            },
        }

    def moe_load(self, state):
        """The expert layers' load so far from an engine's
        ``step_state()`` reading: ``assignments`` ((token, held expert)
        pairs computed), ``tokens`` (tokens routed), ``per_expert [expert
        layers, held]`` and ``load_max_over_mean`` (the busiest held
        expert of any layer against the mean; 1.0 is even routing).
        Publishes what was added since the last call as the counters
        ``serving.moe.assignments`` and ``serving.moe.tokens``, and the
        gauge ``serving.moe.load_max_over_mean``: the records' one
        writer, for one engine a model."""
        return _moe.publish_load(state, self._moe_seen)

    def decode_state(self):
        """Raw device weights for the serving programs (one pytree the
        engine passes through jit unchanged)."""
        return {n: getattr(self, n)._data for n in param_shapes(self.config)}

    # -- the block, once -----------------------------------------------------
    def _rope(self, x, pos):
        """``x [B, T, ..., d_r]`` turned by its positions ``pos [B, T]``,
        each head's dims as two halves (dim ``i`` pairs with ``i +
        d_r/2``); float32 arithmetic, ``x``'s dtype back."""
        c = self.config
        ang = pos[..., None].astype(jnp.float32) * c.inv_freq
        ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3)
                          + ang.shape[-1:])
        cos, sin = jnp.cos(ang) * c.rope_mscale, jnp.sin(ang) * c.rope_mscale
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1).astype(x.dtype)

    def _layers(self, w, h, pos, cache, attend, live=None):
        """Every layer over ``h [B, T, D]`` at positions ``pos [B, T]``.
        ``cache`` is ``(what attend carries, assignments, tokens)``;
        ``attend(carried, i, q_nope, q_pe, c_kv, k_pe, lw) -> (o [B, T, H,
        d_v], carried)`` for layer ``i``: ``q_nope [B, T, H, d_n]`` and
        ``q_pe [B, T, H, d_r]`` scaled and turned, ``c_kv [B, T, R]``
        normalised, ``k_pe [B, T, d_r]`` turned, ``lw`` the layer's
        weights (for ``W_UK``, ``W_UV``).  ``live [B, T]`` bool leaves
        tokens out of the routed experts and their counts."""
        c = self.config
        nD = c.first_k_dense_replace
        nM = c.num_layers - nD
        B, T, D = h.shape
        live = jnp.ones((B, T), bool) if live is None else live

        def at(names, i):
            # one layer's slice of each stacked weight, read where it lies
            return {k: jax.lax.dynamic_index_in_dim(w[k], i, 0, False)
                    for k in names}

        def attention(hh, carried, i):
            lw = at(_ATTN, i)
            a, carried = self._mla(c, lw, _rms(hh, lw["attn_norm_w"],
                                               c.rms_norm_eps),
                                   pos, carried, i, attend)
            hh = hh + a
            return hh, _rms(hh, lw["ffn_norm_w"], c.rms_norm_eps), carried

        carried, counts, tokens = cache
        for i in range(nD):
            h, z, carried = attention(h, carried, i)
            lw = at(_DENSE, i)
            h = h + _swiglu(z, lw["mlp_gu_w"], lw["mlp_down_w"])

        def body(carry, j):
            hh, carried, counts = carry
            hh, z, carried = attention(hh, carried, nD + j)
            f, count = self._expert_ffn(w, j, z.reshape(B * T, D),
                                        live.reshape(B * T))
            counts = jax.lax.dynamic_update_index_in_dim(
                counts, jax.lax.dynamic_index_in_dim(counts, j, 0, False)
                + count, j, 0)
            return (hh + f.reshape(B, T, D), carried, counts), None

        (h, carried, counts), _ = jax.lax.scan(
            body, (h, carried, counts), jnp.arange(nM, dtype=jnp.int32))
        return h, (carried, counts,
                   tokens + live.sum(dtype=tokens.dtype))

    def _expert_ffn(self, w, j, z, live):
        """Expert layer ``j`` over ``z [N, D]``: the shared experts, and
        the routed experts held here for the rows that are ``live``.
        Returns ``(f [N, D], count [held] int32)``."""
        c = self.config
        lw = {k: jax.lax.dynamic_index_in_dim(w[k], j, 0, False)
              for k in _MOE}
        p, expert = _moe.group_limited_top_k(
            jnp.matmul(z, lw["router_w"], preferred_element_type=jnp.float32,
                       precision=matmul_precision()),
            c.n_group, c.topk_group, c.num_experts_per_tok)
        routed, count = _moe.held_expert_ffn(
            z, p * c.routed_scaling_factor, expert, w["expert_gu_w"],
            w["expert_down_w"], c.experts_held[0], j, live)
        return (_swiglu(z, lw["shared_gu_w"], lw["shared_down_w"])
                + routed.astype(z.dtype)), count

    def _mla(self, c, lw, x, pos, carried, i, attend):
        B, T, _ = x.shape
        H, R = c.num_heads, c.kv_lora_rank
        dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        q = _mm(_rms(_mm(x, lw["q_a_w"]), lw["q_a_norm_w"], c.rms_norm_eps),
                lw["q_b_w"])
        q = (q.astype(jnp.float32) * c.softmax_scale).astype(x.dtype)
        q_nope = q[..., :H * dn].reshape(B, T, H, dn)
        q_pe = self._rope(q[..., H * dn:].reshape(B, T, H, dr), pos)
        kv = _mm(x, lw["kv_a_w"])
        c_kv = _rms(kv[..., :R], lw["kv_a_norm_w"], c.rms_norm_eps)
        k_pe = self._rope(kv[..., R:], pos)
        o, carried = attend(carried, i, q_nope, q_pe, c_kv, k_pe, lw)
        return _mm(o.reshape(B, T, H * dv).astype(x.dtype),
                   lw["o_w"]), carried

    def _up(self, lw, c_kv):
        """Per-head keys (their non-rotary part) and values of latent
        rows ``c_kv [..., R]``: ``[..., H, d_n]``, ``[..., H, d_v]``."""
        c = self.config
        H = c.num_heads
        return (_mm(c_kv, lw["kv_b_k_w"]).reshape(c_kv.shape[:-1] + (H, -1)),
                _mm(c_kv, lw["kv_b_v_w"]).reshape(c_kv.shape[:-1] + (H, -1)))

    def _logits(self, w, h_last):
        h_last = _rms(h_last, w["lnf_w"], self.config.rms_norm_eps)
        return _mm(h_last, w["lm_head"]).astype(jnp.float32)

    def _no_counts(self):
        c = self.config
        return (jnp.zeros((c.num_layers - c.first_k_dense_replace,
                           c.experts_held[1]), jnp.int32),
                jnp.zeros((), jnp.int32))

    # -- the plain forward pass ----------------------------------------------
    def forward(self, input_ids):
        """Logits ``[B, T, V]`` of whole sequences, no cache."""
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        return Tensor(self.forward_logits(self.decode_state(),
                                          jnp.asarray(ids)))

    def forward_logits(self, w, ids):
        """Materialised attention over the whole sequence."""
        c = self.config
        B, T = ids.shape
        causal = jnp.tril(jnp.ones((T, T), bool))

        def attend(carried, i, q_nope, q_pe, c_kv, k_pe, lw):
            k_nope, v = self._up(lw, c_kv)
            s = jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                           preferred_element_type=jnp.float32)
            s = s + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe,
                               preferred_element_type=jnp.float32)
            p = jax.nn.softmax(jnp.where(causal, s, NEG_INF), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32), carried

        pos = jnp.broadcast_to(jnp.arange(T), (B, T))
        h, _ = self._layers(w, jnp.take(w["wte"], ids, axis=0), pos,
                            ((),) + self._no_counts(), attend)
        return self._logits(w, h)

    # -- serving entry points (paddle_tpu.serving.LLMEngine, paged) ----------
    def prefill_paged(self, w, ids, start, length, bt, pool, _unused, state,
                      slot, kernel=None):
        """One chunked-prefill step: ``ids [1, C]`` holds ``length`` tokens
        of one request at positions ``[start, start + length)``; ``bt`` is
        its block table, ``pool [kv_layers, n_blocks, bs, row]`` the latent
        pool (the engine's second pool is ``None`` for a latent cache),
        ``state`` the engine's ``step_state`` arrays.  The chunk's rows
        are written first; its queries then attend, materialised, over the
        live rows up-projected a tile at a time, each tile folded into the
        online softmax by ``kernels.mla_attention.mla_prefill_fold`` with
        ``kernel="pallas"`` and by its XLA twin otherwise.  Returns
        ``(pool, None, state, logits [1, V])`` with the logits read at the
        chunk's last live token."""
        c = self.config
        B, C = ids.shape
        H, R = c.num_heads, c.kv_lora_rank
        dr, dv = c.qk_rope_head_dim, c.v_head_dim
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

        fold = (_mla.mla_prefill_fold if _kernel_mode(kernel) == "pallas"
                else _mla.mla_prefill_fold_xla)

        def attend(pool, i, q_nope, q_pe, c_kv, k_pe, lw):
            line = jnp.concatenate([c_kv[0], k_pe[0]], -1)
            line = jnp.where(valid[:, None], line, 0)
            pool = pool.at[i, blk, off].set(jnp.pad(
                line, ((0, 0), (0, row - line.shape[-1]))).astype(
                    pool.dtype))
            q = jnp.swapaxes(jnp.concatenate([q_nope[0], q_pe[0]], -1),
                             0, 1).astype(pool.dtype)        # [H, C, d]
            w_uk = lw["kv_b_k_w"].reshape(R, H, -1)
            w_uv = lw["kv_b_v_w"].reshape(R, H, -1)

            def tile_of(t, state):
                # one tile of live rows, up-projected to every head's keys
                # and values and folded into the online softmax
                blocks = jax.lax.dynamic_slice_in_dim(btp, t * nb_tile,
                                                      nb_tile)
                rows = pool[i, blocks].reshape(tile, row)
                k = jnp.concatenate(
                    [jnp.einsum("kc,chd->hkd", rows[:, :R], w_uk,
                                preferred_element_type=rows.dtype),
                     jnp.broadcast_to(rows[:, R:R + dr], (H, tile, dr))], -1)
                v = jnp.einsum("kc,chd->hkd", rows[:, :R], w_uv,
                               preferred_element_type=rows.dtype)
                return fold(q, k, v, start, t * tile, state)

            _, l, acc = jax.lax.fori_loop(
                0, live_tiles, tile_of,
                (jnp.full((H, C, 1), NEG_INF, jnp.float32),
                 jnp.zeros((H, C, 1), jnp.float32),
                 jnp.zeros((H, C, dv), jnp.float32)))
            return jnp.swapaxes(acc / l, 0, 1)[None], pool

        h = jnp.take(w["wte"], ids, axis=0)
        h, (pool, counts, tokens) = self._layers(
            w, h, tokpos[None], (pool, state["moe_assignments"],
                                 state["moe_tokens"]),
            attend, valid[None])
        h_last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
        return (pool, None, {"moe_assignments": counts, "moe_tokens": tokens},
                self._logits(w, h_last[:, 0]))

    def decode_paged(self, w, tok, pos, bt, pool, _unused, state, running,
                     kernel=None):
        """One decode step for ``B`` slot rows in the absorbed form:
        ``tok``/``pos [B]``, ``bt [B, max_blocks]``, the pool and ``state``
        as in :meth:`prefill_paged`, ``running [B]`` bool.  A row that is
        not running is tabled to the trash block by the engine and is left
        out of the routed experts.  ``kernel="pallas"`` walks the block
        tables (``kernels.mla_attention.mla_decode_attn``); otherwise the
        XLA gather twin.  Returns ``(logits [B, V], pool, None, state)``."""
        c = self.config
        B = tok.shape[0]
        H, R = c.num_heads, c.kv_lora_rank
        bs, row = pool.shape[2], pool.shape[3]
        blk = bt[jnp.arange(B), pos // bs]
        off = pos % bs
        walk = (_mla.mla_decode_attn if _kernel_mode(kernel) == "pallas"
                else _mla.mla_decode_attn_xla)

        def attend(pool, i, q_nope, q_pe, c_kv, k_pe, lw):
            line = jnp.concatenate([c_kv[:, 0], k_pe[:, 0]], -1)
            pad = ((0, 0), (0, row - line.shape[-1]))
            pool = pool.at[i, blk, off].set(
                jnp.pad(line, pad).astype(pool.dtype))
            q_abs = jnp.einsum(
                "bhd,chd->bhc", q_nope[:, 0],
                lw["kv_b_k_w"].reshape(R, H, -1),
                preferred_element_type=jnp.float32).astype(pool.dtype)
            q_row = jnp.concatenate(
                [q_abs, q_pe[:, 0].astype(pool.dtype)], -1)
            lat = walk(jnp.pad(q_row, ((0, 0),) + pad), pool, i, bt, pos, R)
            o = jnp.einsum("bhc,chd->bhd", lat.astype(pool.dtype),
                           lw["kv_b_v_w"].reshape(R, H, -1),
                           preferred_element_type=jnp.float32)
            return o[:, None], pool

        h = jnp.take(w["wte"], tok, axis=0)[:, None, :]
        h, (pool, counts, tokens) = self._layers(
            w, h, pos[:, None], (pool, state["moe_assignments"],
                                 state["moe_tokens"]),
            attend, running[:, None])
        return (self._logits(w, h[:, 0]), pool, None,
                {"moe_assignments": counts, "moe_tokens": tokens})
