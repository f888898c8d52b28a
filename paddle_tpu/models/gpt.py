"""GPT — the flagship hybrid-parallel model.

Reference capability anchor: the GPT-3 recipes trained by the reference's
Fleet stack (SURVEY §3.4, §6 — 1.3B/6.7B, TP×PP×DP×sharding), model code
per-op equivalent to paddlenlp GPT (fused attention + FFN blocks).

TPU-native design decisions:
- **scan-over-layers**: transformer blocks are ONE set of parameters stacked
  on a leading [L] axis, iterated with lax.scan — constant compile time in
  depth, and the natural representation for both remat and pipeline stages.
- **TP/SP/EP via PartitionSpecs**: qkv/fc1 column-sharded, proj/fc2
  row-sharded over 'mp'; activations sequence-sharded over 'sep' (Megatron
  SP); MoE experts sharded over the data axis (EP).  GSPMD inserts the
  psum/all-gather/all-to-all the reference implements as mp_ops/global_scatter.
- **PP via distributed.pipeline**: stacked layers reshape to [pp, L/pp, ...]
  and stream through the collective-permute schedule.
- **flash attention**: Pallas kernel on TPU (kernels/flash_attention.py).
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.dispatch import apply_op, matmul_precision
from ..core.tensor import Parameter, Tensor
from ..distributed.env import get_mesh, hybrid_degrees
from ..distributed.sharding_utils import annotate_param
from ..kernels import paged_attention as _pa
from ..kernels._shapes import NEG_INF
from ..kernels.flash_attention import flash_attention_fwd, reference_attention
from ..kernels.rope import rope_tables
from ..nn.layer.layers import Layer
from ..profiler import host_tracer as _trace


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, max_seq_len=1024, ffn_hidden_size=None,
                 dropout=0.0, attention_dropout=0.0, use_rope=False,
                 layer_norm_epsilon=1e-5, initializer_range=0.02,
                 use_flash_attention=True, recompute=False,
                 sequence_parallel=False, context_parallel=False,
                 num_experts=0, moe_every=2,
                 moe_top_k=2, moe_capacity_factor=1.25,
                 moe_aux_weight=0.01, dtype="float32",
                 tie_word_embeddings=True,
                 pp_schedule="gpipe", virtual_pp_degree=1):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.max_seq_len = max_seq_len
        self.ffn_hidden_size = ffn_hidden_size or 4 * hidden_size
        self.dropout = dropout
        self.attention_dropout = attention_dropout
        self.use_rope = use_rope
        self.layer_norm_epsilon = layer_norm_epsilon
        self.initializer_range = initializer_range
        self.use_flash_attention = use_flash_attention
        self.recompute = recompute
        self.sequence_parallel = sequence_parallel
        # context_parallel: shard the SEQUENCE over the 'sep' mesh axis and
        # run ring attention (kernels/ring_attention.py) — the reference's
        # segment-parallel long-context capability (segment_parallel.py)
        self.context_parallel = context_parallel
        self.num_experts = num_experts
        self.moe_every = moe_every
        self.moe_top_k = moe_top_k
        self.moe_capacity_factor = moe_capacity_factor
        # gate-loss weight folded into the 1F1B objective (the schedule owns
        # the loss there; on GSPMD paths users add moe_aux_loss() manually)
        self.moe_aux_weight = moe_aux_weight
        self.dtype = dtype
        self.tie_word_embeddings = tie_word_embeddings
        # pipeline schedule: 'gpipe' | 'interleaved' (reference:
        # pipeline_parallel.py:1010 VPP) | '1f1b' (reference :459; used via
        # Pipeline1F1BTrainStep, which puts the loss inside the pipeline)
        self.pp_schedule = pp_schedule
        self.virtual_pp_degree = virtual_pp_degree

    # named sizes from the GPT-3 paper / reference recipes
    @staticmethod
    def gpt3_125m(**kw):
        return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)

    @staticmethod
    def gpt3_350m(**kw):
        return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw)

    @staticmethod
    def gpt3_760m(**kw):
        # "GPT-3 Large" — the largest config whose AdamW training state
        # (bf16 params + fp32 master + 2 fp32 moments ~ 10.6 GB) fits a
        # single 16G v5e chip with activation headroom
        return GPTConfig(hidden_size=1536, num_layers=24, num_heads=16, **kw)

    @staticmethod
    def gpt3_1_3b(**kw):
        return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16, **kw)

    @staticmethod
    def gpt3_6_7b(**kw):
        return GPTConfig(hidden_size=4096, num_layers=32, num_heads=32, **kw)


def _sel_policy(mode):
    """Remat policy for selective recompute: which checkpoint_name'd
    activations survive to backward (the rest replay).  The attention
    output is kept once: as the flash kernel's own "flash_out", beside its
    log-sum-exp "flash_lse", so the backward does not replay the kernel;
    as "attn_out" where ring or reference attention produced it."""
    names = ("qkv", "attn_out", "flash_out", "flash_lse")
    if mode != "selective_lean":
        names += ("ffn_up",)
    return jax.checkpoint_policies.save_only_these_names(*names)


def _norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def _dropout(x, key, p):
    """Inverted dropout (shared by the GSPMD block and the manual-TP
    block so the two paths can never drift numerically)."""
    return jnp.where(jax.random.bernoulli(key, 1 - p, x.shape),
                     x / (1 - p), 0.0).astype(x.dtype)


def _lm_logits(c, wte, lnf_w, lnf_b, head, h_last):
    """Final norm + LM head over the last hidden states (shared by
    ``generate`` and the serving prefill/decode entry points)."""
    h_last = _norm(h_last, lnf_w, lnf_b, c.layer_norm_epsilon)
    w = wte.T if c.tie_word_embeddings else head
    return jnp.matmul(h_last, w,
                      precision=matmul_precision()).astype(jnp.float32)


def _rope_rows(x, pos, base=10000.0):
    """apply_rope for single-token rows ``x[B, 1, nh, hd]`` sitting at
    PER-ROW positions ``pos[B]`` (the serving decode twin of
    ``apply_rope(x, offset=pos)``, whose offset is one scalar)."""
    b, s, h, d = x.shape
    inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = pos.astype(jnp.float32)[:, None] * inv[None, :]  # [B, d/2]
    sin = jnp.sin(freqs)[:, None, None, :]
    cos = jnp.cos(freqs)[:, None, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _rope_grid(x, pos, base=10000.0):
    """apply_rope for a grid of tokens ``x[B, T, nh, hd]`` sitting at
    arbitrary PER-TOKEN positions ``pos[B, T]`` (the speculative-verify
    twin of ``_rope_rows``: each draft position gets its own rotation)."""
    b, s, h, d = x.shape
    inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = pos.astype(jnp.float32)[..., None] * inv  # [B, T, d/2]
    sin = jnp.sin(freqs)[:, :, None, :]
    cos = jnp.cos(freqs)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _mm(x, lw, name):
    """Layer matmul against a decode-state weight that may be int8
    weight-only quantized (``quantization.ptq_int8_decode_state`` stores
    ``name`` as int8 plus ``name + "__scale"`` fp32 per-output-channel).
    Per-output-channel scales commute with the contraction, so dequant is
    one row-vector multiply AFTER the matmul — the int8 weight is cast
    (exact: |q| <= 127 fits every float dtype) as it is loaded, never
    rematerialized in full precision in HBM."""
    w = lw[name]
    s = lw.get(name + "__scale")
    if s is None:
        return jnp.matmul(x, w, precision=matmul_precision())
    y = jnp.matmul(x, w.astype(x.dtype), precision=matmul_precision())
    return (y * s).astype(x.dtype)


def _mm_lora(x, lw, name, al, aids):
    """:func:`_mm` plus the gathered batched low-rank update (S-LoRA /
    Punica): ``y + x @ A[ids] @ B[ids]`` where ``al`` holds this layer's
    adapter slabs ``a_<name> [n_slots, d_in, R]`` / ``b_<name>
    [n_slots, R, d_out]`` and ``aids [B]`` is the per-row int32 adapter
    slot — an OPERAND, so one compiled program serves any tenant mix.
    Slot 0 is the base model: its slab rows are zeros AND the row's
    output is selected from the un-adapted ``y`` itself (not ``y + 0``),
    so base rows are bitwise identical to an adapter-free program.
    Composes with the int8 epilogue untouched — the low-rank branch runs
    beside whatever ``_mm`` produced."""
    y = _mm(x, lw, name)
    if al is None:
        return y
    prec = matmul_precision()
    ag = al["a_" + name][aids]                        # [B, d_in, R]
    bg = al["b_" + name][aids]                        # [B, R, d_out]
    d = jnp.einsum("bti,bir->btr", x, ag, precision=prec)
    d = jnp.einsum("btr,bro->bto", d, bg, precision=prec)
    return jnp.where((aids > 0)[:, None, None], y + d.astype(y.dtype), y)


class GPTForCausalLM(Layer):
    def __init__(self, config: GPTConfig):
        t0_ns = time.perf_counter_ns()
        super().__init__()
        self.config = c = config
        import numpy as np
        from ..nn.initializer import Normal, Constant
        from ..nn.functional.init_utils import param_attr_init
        H, L, V, S = c.hidden_size, c.num_layers, c.vocab_size, c.max_seq_len
        F = c.ffn_hidden_size
        init = Normal(0.0, c.initializer_range)
        zeros = Constant(0.0)
        ones = Constant(1.0)
        dt = c.dtype

        def mk(shape, ini, spec):
            p = param_attr_init(shape, jnp.dtype(dt), None, False, ini)
            annotate_param(p, spec)
            return p

        self.wte = mk((V, H), init, P("mp", None))
        if not c.use_rope:
            self.wpe = mk((S, H), init, P())
        self.ln1_w = mk((L, H), ones, P())
        self.ln1_b = mk((L, H), zeros, P())
        self.qkv_w = mk((L, H, 3 * H), init, P(None, None, "mp"))
        self.qkv_b = mk((L, 3 * H), zeros, P(None, "mp"))
        self.proj_w = mk((L, H, H), init, P(None, "mp", None))
        self.proj_b = mk((L, H), zeros, P())
        self.ln2_w = mk((L, H), ones, P())
        self.ln2_b = mk((L, H), zeros, P())
        if c.num_experts > 0:
            E = c.num_experts
            # expert dim shards over 'dp' (EP) only when divisible
            ep = "dp" if E % max(hybrid_degrees().get("dp", 1), 1) == 0 \
                else None
            self.gate_w = mk((L, H, E), init, P())
            self.fc1_w = mk((L, E, H, F), init, P(None, ep, None, "mp"))
            self.fc1_b = mk((L, E, F), zeros, P(None, ep, "mp"))
            self.fc2_w = mk((L, E, F, H), init, P(None, ep, "mp", None))
            self.fc2_b = mk((L, E, H), zeros, P(None, ep, None))
            self._moe_ep_spec = ep
        else:
            self.fc1_w = mk((L, H, F), init, P(None, None, "mp"))
            self.fc1_b = mk((L, F), zeros, P(None, "mp"))
            self.fc2_w = mk((L, F, H), init, P(None, "mp", None))
            self.fc2_b = mk((L, H), zeros, P())
        self.lnf_w = mk((H,), ones, P())
        self.lnf_b = mk((H,), zeros, P())
        if not c.tie_word_embeddings:
            self.lm_head = mk((H, V), init, P(None, "mp"))
        _trace.lifecycle_since("setup.model_init", t0_ns)

    # -- pure block ----------------------------------------------------------
    def _block_fn(self, c, training, dkey):
        from jax.ad_checkpoint import checkpoint_name
        eps = c.layer_norm_epsilon
        nh = c.num_heads
        use_flash = c.use_flash_attention

        def attention(h, lw):
            b, s, H = h.shape
            hd = H // nh
            qkv = jnp.matmul(h, lw["qkv_w"], precision=matmul_precision()) \
                + lw["qkv_b"]
            qkv = checkpoint_name(qkv, "qkv")
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, s, nh, hd)
            k = k.reshape(b, s, nh, hd)
            v = v.reshape(b, s, nh, hd)
            if c.use_rope:
                from ..kernels.rope import apply_rope
                q = apply_rope(q)
                k = apply_rope(k)
            ring = c.context_parallel and hybrid_degrees().get("sep", 1) > 1
            if use_flash and not ring:
                # the kernel names its own output ("flash_out") and lse:
                # kept by the policy, they spare the backward a replay of
                # the kernel, and the output is not kept again as attn_out
                o = flash_attention_fwd(q, k, v, causal=True)
                o = o.reshape(b, s, H)
            else:
                if ring:
                    from ..kernels.ring_attention import ring_attention
                    o = ring_attention(q, k, v, causal=True)
                else:
                    o = reference_attention(q, k, v, causal=True)
                o = checkpoint_name(o.reshape(b, s, H), "attn_out")
            return jnp.matmul(o, lw["proj_w"], precision=matmul_precision()) \
                + lw["proj_b"]

        def ffn(h, lw):
            if c.num_experts > 0:
                # real top-k expert dispatch (EP): GShard one-hot
                # dispatch/combine einsums over a static capacity; the
                # expert dim is sharded over 'dp', so GSPMD inserts the
                # token all-to-all (the reference's global_scatter/
                # global_gather, moe/moe_layer.py:263).  Compute is
                # O(top_k) per token, not O(E).
                from ..incubate.moe import moe_ffn
                return moe_ffn(
                    h, lw["gate_w"], lw["fc1_w"], lw["fc1_b"],
                    lw["fc2_w"], lw["fc2_b"], top_k=c.moe_top_k,
                    capacity_factor=c.moe_capacity_factor,
                    ep_spec=getattr(self, "_moe_ep_spec", None))
            up = jnp.matmul(h, lw["fc1_w"], precision=matmul_precision()) \
                + lw["fc1_b"]
            up = checkpoint_name(up, "ffn_up")
            act = jax.nn.gelu(up)
            out = jnp.matmul(act, lw["fc2_w"],
                             precision=matmul_precision()) + lw["fc2_b"]
            return out, None

        drop = c.dropout if training else 0.0

        def block(h, lw_and_key):
            """Returns (h, aux): aux is the MoE load-balancing loss for this
            layer (None for dense FFN)."""
            lw, key = lw_and_key
            x = _norm(h, lw["ln1_w"], lw["ln1_b"], eps)
            a = attention(x, lw)
            if drop > 0:
                key, k1 = jax.random.split(key)
                a = _dropout(a, k1, drop)
            h = h + a
            x = _norm(h, lw["ln2_w"], lw["ln2_b"], eps)
            f, aux = ffn(x, lw)
            if drop > 0:
                key, k2 = jax.random.split(key)
                f = _dropout(f, k2, drop)
            h = h + f
            if c.sequence_parallel:
                mesh = get_mesh()
                if mesh is not None and isinstance(h, jax.core.Tracer):
                    h = jax.lax.with_sharding_constraint(
                        h, jax.sharding.NamedSharding(
                            mesh, P(("dp", "sharding"), "sep", None)))
            return h, aux

        return block

    def _stacked(self):
        names = ["ln1_w", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
                 "ln2_w", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b"]
        if self.config.num_experts > 0:
            names.append("gate_w")
        return names

    def forward(self, input_ids, position_ids=None):
        c = self.config
        training = self.training
        names = self._stacked()
        params = [getattr(self, n) for n in names]
        from ..tensor.random import _next_key
        dkey = _next_key() if (training and c.dropout > 0) else None
        pp = hybrid_degrees().get("pp", 1)

        def fn(ids, wte, lnf_w, lnf_b, *rest, head_w=None):
            L = c.num_layers
            if c.use_rope:
                wpe = None
                stacked = rest
            else:
                wpe = rest[0]
                stacked = rest[1:]
            lws = dict(zip(names, stacked))
            h = jnp.take(wte, ids, axis=0)
            if wpe is not None:
                pos = jnp.arange(ids.shape[1])
                h = h + jnp.take(wpe, pos, axis=0)
            block = self._block_fn(c, training, dkey)
            keys = (jax.random.split(dkey, L) if dkey is not None
                    else jnp.zeros((L, 2), jnp.uint32))

            if pp > 1:
                from ..distributed.pipeline import pipeline_apply
                V = (c.virtual_pp_degree
                     if c.pp_schedule == "interleaved" else 1)
                n_stage = pp * max(V, 1)
                if L % n_stage != 0:
                    raise ValueError(
                        f"pipeline parallel requires num_layers ({L}) "
                        f"divisible by pp*virtual_pp ({n_stage})")
                lpp = L // n_stage

                moe = c.num_experts > 0

                def stage_fn(sp, hh):
                    # aux (MoE load-balancing loss) rides the pipeline via
                    # pipeline_apply(with_aux=True) instead of being dropped;
                    # per-layer dropout keys travel in sp ('__keys') so each
                    # layer gets an independent mask (matching the pp=1 scan)
                    def body(carry, xs):
                        hh, aux_sum = carry
                        lw = {k: v for k, v in xs.items() if k != "__keys"}
                        key = xs["__keys"] if dkey is not None else None
                        hh, aux = block(hh, (lw, key))
                        if aux is not None:
                            aux_sum = aux_sum + aux
                        return (hh, aux_sum), None
                    (hh, aux), _ = jax.lax.scan(
                        body, (hh, jnp.zeros((), jnp.float32)), sp)
                    return (hh, aux) if moe else hh
                stage_params = {n: v.reshape(n_stage, lpp, *v.shape[1:])
                                for n, v in lws.items()}
                stage_params["__keys"] = keys.reshape(n_stage, lpp, 2)
                M = max(2 * pp, 1)
                # microbatches must divide batch
                while ids.shape[0] % M != 0 and M > 1:
                    M -= 1
                if M < 2 * pp:
                    import warnings
                    warnings.warn(
                        f"pipeline microbatches degraded to {M} (batch "
                        f"{ids.shape[0]} not divisible by {2 * pp}); bubble "
                        f"fraction increases — prefer batch % {2 * pp} == 0",
                        RuntimeWarning, stacklevel=2)
                sel_policy = (_sel_policy(c.recompute) if c.recompute in
                              ("selective", "selective_lean") else None)
                h = pipeline_apply(stage_fn, stage_params, h, M,
                                   remat=bool(c.recompute),
                                   schedule=c.pp_schedule
                                   if c.pp_schedule == "interleaved"
                                   else "gpipe",
                                   num_chunks=max(V, 1),
                                   remat_policy=sel_policy,
                                   with_aux=moe)
                if moe:
                    h, aux_pp = h
            else:
                def body(hh, xs):
                    lw, key = xs
                    hh, aux = block(hh, (lw, key))
                    return hh, (aux if aux is not None
                                else jnp.zeros((), jnp.float32))
                scan_body = body
                if c.recompute in ("selective", "selective_lean"):
                    # Megatron-style selective recompute (reference:
                    # fleet/recompute 'full' vs refined recompute): save the
                    # expensive matmul outputs and flash's output and lse;
                    # ln/gelu and the output projection replay in bwd, the
                    # flash forward does not.  'selective_lean' also drops
                    # the 4H-wide ffn_up (fc1 replays in bwd, ~+4% step
                    # FLOPs) — it buys a bigger batch at 760M+.
                    scan_body = jax.checkpoint(
                        body, policy=_sel_policy(c.recompute))
                elif c.recompute:
                    scan_body = jax.checkpoint(body)
                h, auxs = jax.lax.scan(scan_body, h, (lws, keys))
            h = _norm(h, lnf_w, lnf_b, c.layer_norm_epsilon)
            if c.tie_word_embeddings:
                logits = jnp.matmul(h, wte.T, precision=matmul_precision())
            else:
                logits = jnp.matmul(h, head_w,
                                    precision=matmul_precision())
            mesh = get_mesh()
            if mesh is not None and isinstance(logits, jax.core.Tracer):
                logits = jax.lax.with_sharding_constraint(
                    logits, jax.sharding.NamedSharding(
                        mesh, P(("dp", "sharding"), None, "mp")))
            if c.num_experts > 0:
                return logits, (aux_pp if pp > 1 else jnp.sum(auxs))
            return logits

        args = [input_ids, self.wte, self.lnf_w, self.lnf_b]
        if not c.use_rope:
            args.append(self.wpe)
        args += params
        if not c.tie_word_embeddings:
            out = apply_op("gpt_forward",
                           lambda ids, wte, lw, lb, *st: fn(
                               ids, wte, lw, lb, *st[:-1], head_w=st[-1]),
                           *args, self.lm_head)
        else:
            out = apply_op("gpt_forward", fn, *args)
        if isinstance(out, tuple):
            logits, self._moe_aux = out
            return logits
        self._moe_aux = None
        return out

    def moe_aux_loss(self):
        """Summed MoE load-balancing loss from the last forward (0 when the
        model is dense).  Carried through the pipeline schedules via
        pipeline_apply(with_aux=True).  Add `model.moe_aux_loss() * coeff`
        to the training loss (reference trainers do the same with the gate
        loss, moe/moe_layer.py)."""
        if getattr(self, "_moe_aux", None) is None:
            return Tensor._wrap(jnp.zeros((), jnp.float32))
        return self._moe_aux


    # -- generation (KV-cached decode) ---------------------------------------
    def _cached_layers(self, c, lws, h, cache_k, cache_v, pos):
        """Run all blocks on h [B, T, H] writing K/V into the caches at
        positions [pos, pos+T) and attending to everything <= query pos.

        cache_k/cache_v: [L, B, S, nh, hd].  This is the decode twin of the
        training block (reference: masked_multihead_attention_kernel.cu /
        fused_multi_transformer's CacheKV path) — one fused scan over
        layers, dense O(S) attention against the cache, MXU-friendly
        static shapes."""
        nh = c.num_heads
        eps = c.layer_norm_epsilon
        B, T, H = h.shape
        S = cache_k.shape[2]
        hd = H // nh
        scale = 1.0 / math.sqrt(hd)
        kpos = jnp.arange(S)
        qpos = pos + jnp.arange(T)
        mask = kpos[None, :] <= qpos[:, None]          # [T, S]

        def body(hh, xs):
            lw, ck, cv = xs
            x = _norm(hh, lw["ln1_w"], lw["ln1_b"], eps)
            qkv = _mm(x, lw, "qkv_w") + lw["qkv_b"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, T, nh, hd)
            k = k.reshape(B, T, nh, hd)
            v = v.reshape(B, T, nh, hd)
            if c.use_rope:
                from ..kernels.rope import apply_rope
                q = apply_rope(q, offset=pos)
                k = apply_rope(k, offset=pos)
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, pos, 0, 0))
            logits = jnp.einsum("bqhd,bkhd->bhqk",
                                (q * scale).astype(jnp.float32),
                                ck.astype(jnp.float32))
            logits = jnp.where(mask[None, None], logits, NEG_INF)
            p = jax.nn.softmax(logits, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(cv.dtype), cv)
            o = o.reshape(B, T, H)
            a = _mm(o, lw, "proj_w") + lw["proj_b"]
            hh = hh + a
            x = _norm(hh, lw["ln2_w"], lw["ln2_b"], eps)
            if c.num_experts > 0:
                from ..incubate.moe import moe_ffn
                f, _aux = moe_ffn(
                    x, lw["gate_w"], lw["fc1_w"], lw["fc1_b"],
                    lw["fc2_w"], lw["fc2_b"], top_k=c.moe_top_k,
                    capacity_factor=c.moe_capacity_factor)
            else:
                up = _mm(x, lw, "fc1_w") + lw["fc1_b"]
                f = _mm(jax.nn.gelu(up), lw, "fc2_w") + lw["fc2_b"]
            return hh + f, (ck, cv)

        h, (cache_k, cache_v) = jax.lax.scan(body, h,
                                             (lws, cache_k, cache_v))
        return h, cache_k, cache_v

    def _embed(self, c, wte, wpe, ids, pos):
        h = jnp.take(wte, ids, axis=0)
        if wpe is not None:
            h = h + jax.lax.dynamic_slice_in_dim(wpe, pos, ids.shape[1],
                                                 axis=0)
        return h

    # -- serving entry points (paddle_tpu.serving.LLMEngine) -----------------
    def cache_spec(self):
        """What a serving engine holds for one request: K/V of every
        layer, and no per-slot state beside it (see
        ``models/olmo_hybrid.py`` for a model that has some)."""
        c = self.config
        return {"kv_layers": c.num_layers, "kv_heads": c.num_heads,
                "head_dim": c.hidden_size // c.num_heads, "slot_state": {}}

    def decode_state(self):
        """Raw device weights for the serving prefill/decode programs (one
        dict the engine passes through jit unchanged — the arrays stay
        device-resident, never re-hydrated per step)."""
        c = self.config
        return {
            "lws": {n: getattr(self, n)._data for n in self._stacked()},
            "wte": self.wte._data,
            "wpe": None if c.use_rope else self.wpe._data,
            "lnf_w": self.lnf_w._data,
            "lnf_b": self.lnf_b._data,
            "head": (None if c.tie_word_embeddings else self.lm_head._data),
        }

    def prefill_paged(self, w, ids, start, length, bt, pool_k, pool_v,
                      scale_k=None, scale_v=None, adapters=None,
                      adapter_ids=None):
        """One chunked-prefill step over a block-pool KV arena (the
        serving twin of ``_cached_layers``; see ``serving.paged``).

        ``ids[1, C]`` is one right-padded prompt chunk of true length
        ``length`` (traced scalar) whose tokens sit at logical positions
        ``[start, start + length)``; ``bt[max_blocks]`` is the request's
        int32 block table (an OPERAND — the program shape depends only
        on the chunk bucket ``C``); ``pool_k``/``pool_v`` are the shared
        donated pool ``[L, n_blocks, bs, nh, hd]``.  Each chunk token's
        K/V is scattered into block ``bt[(start+i) // bs]`` at offset
        ``(start+i) % bs``; padded tail tokens are zeroed and routed to
        the trash block 0.  Attention gathers the row's whole logical
        sequence ``bt -> [max_blocks*bs, nh, hd]`` AFTER the scatter, so
        one masked ``kpos <= qpos`` einsum covers the cached prefix
        (earlier chunks, shared prefix blocks) and the chunk itself.
        Returns ``(pool_k, pool_v, logits[1, V])`` with the fp32 logits
        read at the chunk's last valid token — the first-token sample
        point when this is the final chunk.

        Quantized-KV mode: when the engine passes per-token fp32 scale
        arenas ``scale_k``/``scale_v [L, n_blocks, bs]`` (pool dtype
        int8/fp8), each token's K/V is quantized on insert
        (``kernels.paged_attention.quantize_kv``) and the gathered view
        is dequantized for the chunk attention; the return grows to
        ``(pool_k, pool_v, scale_k, scale_v, logits)``."""
        c = self.config
        nh = c.num_heads
        eps = c.layer_norm_epsilon
        H = c.hidden_size
        hd = H // nh
        B, C = ids.shape
        n_blocks, bs = pool_k.shape[1], pool_k.shape[2]
        nhp = pool_k.shape[3]       # the pool's heads: nh in whole tiles
        ph, uh = _pa.head_padding(pool_k, nh)
        max_blocks = bt.shape[0]
        S = max_blocks * bs
        scale = 1.0 / math.sqrt(hd)
        h = self._embed(c, w["wte"], w["wpe"], ids, start)
        valid = jnp.arange(C) < length
        tokpos = start + jnp.arange(C)
        # padded tokens scatter (zeroed) into the trash block 0
        blk = jnp.where(valid, bt[tokpos // bs], 0)
        off = tokpos % bs
        kpos = jnp.arange(S)
        qpos = start + jnp.arange(C)
        mask = kpos[None, :] <= qpos[:, None]              # [C, S]
        quant = scale_k is not None
        kv_dt = _pa.kv_dtype_of(pool_k.dtype) if quant else None

        lora = adapters is not None
        aids = adapter_ids

        def body(hh, xs):
            if lora:
                lw, al, *rest = xs
            else:
                al = None
                lw, *rest = xs
            if quant:
                ck, cv, sk, sv = rest
            else:
                ck, cv = rest
                sk = sv = None
            x = _norm(hh, lw["ln1_w"], lw["ln1_b"], eps)
            qkv = _mm_lora(x, lw, "qkv_w", al, aids) + lw["qkv_b"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, C, nh, hd)
            k = k.reshape(B, C, nh, hd)
            v = v.reshape(B, C, nh, hd)
            if c.use_rope:
                from ..kernels.rope import apply_rope
                q = apply_rope(q, offset=start)
                k = apply_rope(k, offset=start)
            vm = valid[:, None, None]
            if quant:
                # quantize on insert: tiles in the arena dtype, one fp32
                # scale per token riding the scale arena at the same
                # (block, offset) address
                kq, ks = _pa.quantize_kv(k[0], kv_dt)
                vq, vs = _pa.quantize_kv(v[0], kv_dt)
                kz = jnp.where(vm, kq, jnp.zeros((), ck.dtype))
                vz = jnp.where(vm, vq, jnp.zeros((), cv.dtype))
                sk = sk.at[blk, off].set(jnp.where(valid, ks, 0.0))
                sv = sv.at[blk, off].set(jnp.where(valid, vs, 0.0))
            else:
                kz = jnp.where(vm, k[0].astype(ck.dtype), 0)
                vz = jnp.where(vm, v[0].astype(cv.dtype), 0)
            ck = ck.at[blk, off].set(ph(kz))
            cv = cv.at[blk, off].set(ph(vz))
            # gather AFTER the scatter: the logical view holds the shared
            # prefix, earlier chunks, and this chunk's own K/V
            if quant:
                gk = uh(_pa.dequantize_kv(ck[bt], sk[bt]).reshape(
                    S, nhp, hd))[None]
                gv = uh(_pa.dequantize_kv(cv[bt], sv[bt]).reshape(
                    S, nhp, hd))[None]
            else:
                gk = uh(ck[bt].reshape(S, nhp, hd))[None]
                gv = uh(cv[bt].reshape(S, nhp, hd))[None]
            logits = jnp.einsum("bqhd,bkhd->bhqk",
                                (q * scale).astype(jnp.float32),
                                gk.astype(jnp.float32))
            logits = jnp.where(mask[None, None], logits, NEG_INF)
            p = jax.nn.softmax(logits, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(gv.dtype), gv)
            o = o.reshape(B, C, H).astype(hh.dtype)
            a = _mm_lora(o, lw, "proj_w", al, aids) + lw["proj_b"]
            hh = hh + a
            x = _norm(hh, lw["ln2_w"], lw["ln2_b"], eps)
            if c.num_experts > 0:
                from ..incubate.moe import moe_ffn
                f, _aux = moe_ffn(
                    x, lw["gate_w"], lw["fc1_w"], lw["fc1_b"],
                    lw["fc2_w"], lw["fc2_b"], top_k=c.moe_top_k,
                    capacity_factor=c.moe_capacity_factor)
            else:
                up = _mm_lora(x, lw, "fc1_w", al, aids) + lw["fc1_b"]
                f = _mm_lora(jax.nn.gelu(up), lw, "fc2_w", al,
                             aids) + lw["fc2_b"]
            return hh + f, ((ck, cv, sk, sv) if quant else (ck, cv))

        xs = ((w["lws"], adapters) if lora else (w["lws"],)) \
            + ((pool_k, pool_v, scale_k, scale_v) if quant
               else (pool_k, pool_v))
        if quant:
            h, (pool_k, pool_v, scale_k, scale_v) = jax.lax.scan(
                body, h, xs)
        else:
            h, (pool_k, pool_v) = jax.lax.scan(body, h, xs)
        h_last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
        logits = _lm_logits(c, w["wte"], w["lnf_w"], w["lnf_b"], w["head"],
                            h_last[:, 0])
        if quant:
            return pool_k, pool_v, scale_k, scale_v, logits
        return pool_k, pool_v, logits

    def decode_paged(self, w, tok, pos, bt, pool_k, pool_v,
                     scale_k=None, scale_v=None, kernel=None,
                     mesh=None, head_axis=None, adapters=None,
                     adapter_ids=None):
        """One decode step for B independent slot rows at PER-ROW
        positions over the block-pool arena (the serving twin of
        ``_cached_layers``, whose position is one scalar for the whole
        batch and whose cache row is replaced by a block-table gather).
        Rows are independent, so a slot's trajectory is token-identical
        to a ``generate`` call decoding the same request alone.

        tok ``[B]`` int32, pos ``[B]`` int32, bt ``[B, max_blocks]``
        int32 block tables (operands: the ONE compiled decode program
        serves every block-table content), pool_k/pool_v ``[L, n_blocks,
        bs, nh, hd]``.  Each row writes its K/V into block
        ``bt[row, pos // bs]`` at offset ``pos % bs`` (rows with nothing
        to write are tabled to the trash block 0 by the engine) and
        attends over its gathered logical sequence with ``kpos <=
        pos[row]``.  Returns ``(logits [B, V] fp32, pool_k, pool_v)``.

        The pools ride the layer scan as carry and are updated in place:
        each layer scatters at ``(l, blk, off)`` into the stacked buffers
        and no layer's slice of them is ever materialised.

        ``kernel="pallas"`` (what ``kernels.paged_attention.kernel_mode``
        resolves to on a TPU) routes the attention through the fused
        Pallas block-table walk — same operands, same mask, only the
        live blocks read, no ``[B, S]`` logical view in HBM.
        ``kernel=None``/``"off"`` is the plain-XLA gather below: the
        CPU path and the tests' reference.  Under tensor parallelism
        pass ``mesh``/``head_axis`` (the serving arena does): the pallas
        call then runs through ``shard_map`` over the KV head axis —
        each chip walks only its own ``nh/mp`` heads, and the cross-chip
        reduction happens at the following proj contraction exactly as
        in the gather twin (GSPMD partitions that twin with no help).
        Quantized-KV mode mirrors
        ``prefill_paged``: per-token fp32 scale arenas ``scale_k``/
        ``scale_v [L, n_blocks, bs]`` ride the donated carry, the new
        token quantizes on insert, and the return grows to ``(logits,
        pool_k, pool_v, scale_k, scale_v)``."""
        c = self.config
        nh = c.num_heads
        eps = c.layer_norm_epsilon
        H = c.hidden_size
        hd = H // nh
        B = tok.shape[0]
        n_blocks, bs = pool_k.shape[1], pool_k.shape[2]
        nhp = pool_k.shape[3]       # the pool's heads: nh in whole tiles
        ph, uh = _pa.head_padding(pool_k, nh)
        max_blocks = bt.shape[1]
        S = max_blocks * bs
        scale = 1.0 / math.sqrt(hd)
        h = jnp.take(w["wte"], tok, axis=0)[:, None, :]
        if w["wpe"] is not None:
            h = h + jnp.take(w["wpe"], pos, axis=0)[:, None, :]
        kpos = jnp.arange(S)
        mask = kpos[None, :] <= pos[:, None]                     # [B, S]
        rows = jnp.arange(B)
        blk = bt[rows, pos // bs]                                # [B]
        off = pos % bs
        quant = scale_k is not None
        kv_dt = _pa.kv_dtype_of(pool_k.dtype) if quant else None
        mode = kernel or "off"
        if mode not in ("off", "pallas"):
            raise ValueError(f"decode_paged: kernel={mode!r}")
        _pa.note_program(mode)

        lora = adapters is not None
        aids = adapter_ids

        def body(carry, xs):
            # the arena rides the scan as CARRY: each layer scatters its
            # new token into the stacked buffers at (l, blk, off) and
            # reads them where they lie — no layer's slice of the pool
            # is ever copied out or back
            hh, ck, cv, *scales = carry
            sk, sv = scales if quant else (None, None)
            if lora:
                lw, l, al = xs
            else:
                lw, l = xs
                al = None
            x = _norm(hh, lw["ln1_w"], lw["ln1_b"], eps)
            qkv = _mm_lora(x, lw, "qkv_w", al, aids) + lw["qkv_b"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, 1, nh, hd)
            k = k.reshape(B, 1, nh, hd)
            v = v.reshape(B, 1, nh, hd)
            if c.use_rope:
                q = _rope_rows(q, pos)
                k = _rope_rows(k, pos)
            if quant:
                kq, ks = _pa.quantize_kv(k[:, 0], kv_dt)
                vq, vs = _pa.quantize_kv(v[:, 0], kv_dt)
                ck = ck.at[l, blk, off].set(ph(kq))
                cv = cv.at[l, blk, off].set(ph(vq))
                sk = sk.at[l, blk, off].set(ks)
                sv = sv.at[l, blk, off].set(vs)
            else:
                ck = ck.at[l, blk, off].set(ph(k[:, 0]).astype(ck.dtype))
                cv = cv.at[l, blk, off].set(ph(v[:, 0]).astype(cv.dtype))
            if mode == "pallas":
                # fused block-table walk: the arena is read in physical
                # blocks, never gathered to [B, S]
                if mesh is not None and head_axis is not None:
                    o = _pa.sharded_paged_decode_attention(
                        mesh, head_axis, q[:, 0], ck, cv, l, bt, pos, sk,
                        sv, scale=scale)
                else:
                    o = uh(_pa.paged_decode_attention(
                        ph(q[:, 0]), ck, cv, l, bt, pos, sk, sv,
                        scale=scale))
                o = o.reshape(B, 1, H)
            else:
                # K/V go to the contractions in the dtype they are
                # stored in, accumulated in fp32: a product of two bf16
                # values is exact in fp32, so this is the arithmetic of
                # a widened copy without the copy
                gk = uh(ck[l, bt].reshape(B, S, nhp, hd))
                gv = uh(cv[l, bt].reshape(B, S, nhp, hd))
                if quant:
                    gk = _pa.dequantize_kv(gk, sk[l, bt].reshape(B, S))
                    gv = _pa.dequantize_kv(gv, sv[l, bt].reshape(B, S))
                logits = jnp.einsum("bqhd,bkhd->bhqk",
                                    (q * scale).astype(gk.dtype), gk,
                                    preferred_element_type=jnp.float32)
                logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
                p = jax.nn.softmax(logits, axis=-1)
                o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(gv.dtype), gv,
                               preferred_element_type=jnp.float32)
                o = o.reshape(B, 1, H)
            o = o.astype(hh.dtype)
            a = _mm_lora(o, lw, "proj_w", al, aids) + lw["proj_b"]
            hh = hh + a
            x = _norm(hh, lw["ln2_w"], lw["ln2_b"], eps)
            if c.num_experts > 0:
                from ..incubate.moe import moe_ffn
                f, _aux = moe_ffn(
                    x, lw["gate_w"], lw["fc1_w"], lw["fc1_b"],
                    lw["fc2_w"], lw["fc2_b"], top_k=c.moe_top_k,
                    capacity_factor=c.moe_capacity_factor)
            else:
                up = _mm_lora(x, lw, "fc1_w", al, aids) + lw["fc1_b"]
                f = _mm_lora(jax.nn.gelu(up), lw, "fc2_w", al,
                             aids) + lw["fc2_b"]
            return (hh + f, ck, cv) + ((sk, sv) if quant else ()), None

        xs = (w["lws"], jnp.arange(c.num_layers, dtype=jnp.int32)) \
            + ((adapters,) if lora else ())
        pools = (pool_k, pool_v) + ((scale_k, scale_v) if quant else ())
        (h, *pools), _ = jax.lax.scan(body, (h, *pools), xs)
        logits = _lm_logits(c, w["wte"], w["lnf_w"], w["lnf_b"], w["head"],
                            h[:, 0])
        return (logits, *pools)

    def verify_paged(self, w, toks, pos0, n_valid, bt, pool_k, pool_v,
                     scale_k=None, scale_v=None, adapters=None,
                     adapter_ids=None):
        """Speculative-decoding verify step: score K+1 token positions
        per row in ONE program over the block-pool arena (the multi-query
        sibling of ``decode_paged``; see ``serving.speculative``).

        ``toks[B, K1]`` holds each row's last committed token followed by
        K draft proposals; ``pos0[B]`` is the committed token's position,
        so ``toks[b, j]`` sits at logical position ``pos0[b] + j``.
        ``n_valid[B]`` (1..K1) caps how many of the K1 positions are real
        for the row — writes for ``j >= n_valid`` are routed to the trash
        block 0 so a row near its token budget can ride the same
        fixed-shape program without its KV overrunning the blocks the
        admission reservation pinned.  Each valid token's K/V is
        scattered at ``bt[b, (pos0+j) // bs]`` offset ``(pos0+j) % bs``
        (overwriting any stale rejected-draft KV from earlier rounds —
        rollback never copies), and query ``j`` attends its own causal
        prefix ``kpos <= pos0 + j`` over the gathered logical sequence.
        Returns ``(logits[B, K1, V] fp32, pool_k, pool_v)`` — logits at
        EVERY drafted position, from which the engine's acceptance rule
        keeps a prefix of the draft and samples the correction/bonus
        token.  Quantized-KV mode mirrors ``decode_paged``: per-token
        fp32 scale arenas ride the donated carry and the return grows to
        ``(logits, pool_k, pool_v, scale_k, scale_v)``."""
        c = self.config
        nh = c.num_heads
        eps = c.layer_norm_epsilon
        H = c.hidden_size
        hd = H // nh
        B, K1 = toks.shape
        n_blocks, bs = pool_k.shape[1], pool_k.shape[2]
        nhp = pool_k.shape[3]       # the pool's heads: nh in whole tiles
        ph, uh = _pa.head_padding(pool_k, nh)
        max_blocks = bt.shape[1]
        S = max_blocks * bs
        scale = 1.0 / math.sqrt(hd)
        pos = pos0[:, None] + jnp.arange(K1)[None, :]            # [B, K1]
        valid = jnp.arange(K1)[None, :] < n_valid[:, None]       # [B, K1]
        h = jnp.take(w["wte"], toks, axis=0)                     # [B, K1, H]
        if w["wpe"] is not None:
            h = h + jnp.take(w["wpe"], jnp.minimum(pos, w["wpe"].shape[0] - 1),
                             axis=0)
        rows = jnp.arange(B)
        # invalid positions may index past the table; the where() routes
        # them to the trash block before any write can land
        blk = jnp.where(valid, bt[rows[:, None],
                                  jnp.minimum(pos // bs, max_blocks - 1)], 0)
        off = pos % bs
        kpos = jnp.arange(S)
        mask = kpos[None, None, :] <= pos[:, :, None]            # [B, K1, S]
        quant = scale_k is not None
        kv_dt = _pa.kv_dtype_of(pool_k.dtype) if quant else None

        lora = adapters is not None
        aids = adapter_ids

        def body(hh, xs):
            if lora:
                lw, al, *rest = xs
            else:
                al = None
                lw, *rest = xs
            if quant:
                ck, cv, sk, sv = rest
            else:
                ck, cv = rest
                sk = sv = None
            x = _norm(hh, lw["ln1_w"], lw["ln1_b"], eps)
            qkv = _mm_lora(x, lw, "qkv_w", al, aids) + lw["qkv_b"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, K1, nh, hd)
            k = k.reshape(B, K1, nh, hd)
            v = v.reshape(B, K1, nh, hd)
            if c.use_rope:
                q = _rope_grid(q, pos)
                k = _rope_grid(k, pos)
            if quant:
                kq, ks = _pa.quantize_kv(k, kv_dt)
                vq, vs = _pa.quantize_kv(v, kv_dt)
                ck = ck.at[blk, off].set(ph(kq))
                cv = cv.at[blk, off].set(ph(vq))
                sk = sk.at[blk, off].set(ks)
                sv = sv.at[blk, off].set(vs)
            else:
                ck = ck.at[blk, off].set(ph(k).astype(ck.dtype))
                cv = cv.at[blk, off].set(ph(v).astype(cv.dtype))
            # gather AFTER the scatter: query j sees the committed prefix
            # plus every draft token at or before its own position
            if quant:
                gk = uh(_pa.dequantize_kv(ck[bt], sk[bt]).reshape(
                    B, S, nhp, hd))
                gv = uh(_pa.dequantize_kv(cv[bt], sv[bt]).reshape(
                    B, S, nhp, hd))
            else:
                gk = uh(ck[bt].reshape(B, S, nhp, hd))
                gv = uh(cv[bt].reshape(B, S, nhp, hd))
            logits = jnp.einsum("bqhd,bkhd->bhqk",
                                (q * scale).astype(jnp.float32),
                                gk.astype(jnp.float32))
            logits = jnp.where(mask[:, None], logits, NEG_INF)
            p = jax.nn.softmax(logits, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(gv.dtype), gv)
            o = o.reshape(B, K1, H).astype(hh.dtype)
            a = _mm_lora(o, lw, "proj_w", al, aids) + lw["proj_b"]
            hh = hh + a
            x = _norm(hh, lw["ln2_w"], lw["ln2_b"], eps)
            if c.num_experts > 0:
                from ..incubate.moe import moe_ffn
                f, _aux = moe_ffn(
                    x, lw["gate_w"], lw["fc1_w"], lw["fc1_b"],
                    lw["fc2_w"], lw["fc2_b"], top_k=c.moe_top_k,
                    capacity_factor=c.moe_capacity_factor)
            else:
                up = _mm_lora(x, lw, "fc1_w", al, aids) + lw["fc1_b"]
                f = _mm_lora(jax.nn.gelu(up), lw, "fc2_w", al,
                             aids) + lw["fc2_b"]
            return hh + f, ((ck, cv, sk, sv) if quant else (ck, cv))

        xs = ((w["lws"], adapters) if lora else (w["lws"],)) \
            + ((pool_k, pool_v, scale_k, scale_v) if quant
               else (pool_k, pool_v))
        if quant:
            h, (pool_k, pool_v, scale_k, scale_v) = jax.lax.scan(
                body, h, xs)
        else:
            h, (pool_k, pool_v) = jax.lax.scan(body, h, xs)
        logits = _lm_logits(c, w["wte"], w["lnf_w"], w["lnf_b"], w["head"],
                            h)
        if quant:
            return logits, pool_k, pool_v, scale_k, scale_v
        return logits, pool_k, pool_v

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 seed=None):
        """Autoregressive decoding with a static KV cache, fully compiled
        (prefill + lax.scan decode loop in ONE XLA program).

        Reference analogue: the fused decode path
        (masked_multihead_attention_kernel.cu + paddlenlp generate);
        TPU-native: static cache shapes, dynamic_update_slice writes,
        whole loop under jit.  Returns [B, T + max_new_tokens] token ids
        (after eos, the row keeps emitting eos).  Sampling shares
        ``serving.sampling`` with the continuous-batching engine, so
        ``serving.LLMEngine`` reproduces this method token for token."""
        c = self.config
        names = self._stacked()
        lws = {n: getattr(self, n)._data for n in names}
        wte = self.wte._data
        wpe = self.wpe._data if not c.use_rope else None
        head = (None if c.tie_word_embeddings else self.lm_head._data)
        lnf_w, lnf_b = self.lnf_w._data, self.lnf_b._data
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        B, T = ids.shape
        S = T + int(max_new_tokens)
        if S > c.max_seq_len and not c.use_rope:
            raise ValueError(f"generation length {S} exceeds max_seq_len "
                             f"{c.max_seq_len}")
        from ..tensor.random import _DEFAULT_GEN
        key = (jax.random.key(seed) if seed is not None
               else _DEFAULT_GEN.next_key())
        eos = -1 if eos_token_id is None else int(eos_token_id)

        from ..serving.sampling import sample_tokens

        def logits_of(h_last):
            return _lm_logits(c, wte, lnf_w, lnf_b, head, h_last)

        # normalize the sampling knobs to host scalars once, outside the
        # traced body — they are trace-time constants, not traced values
        do_sample = bool(do_sample)
        temperature = float(temperature)
        top_k, top_p = int(top_k), float(top_p)

        def sample(lg, k):
            return sample_tokens(lg, k, do_sample=do_sample,
                                 temperature=temperature,
                                 top_k=top_k, top_p=top_p,
                                 out_dtype=ids.dtype)

        def run(lws, wte, wpe, lnf_w, lnf_b, head, ids, key):
            nh, H = c.num_heads, c.hidden_size
            hd = H // nh
            dt = jnp.dtype(c.dtype)
            ck0 = jnp.zeros((c.num_layers, B, S, nh, hd), dt)
            cv0 = jnp.zeros((c.num_layers, B, S, nh, hd), dt)
            h = self._embed(c, wte, wpe, ids, 0)
            h, ck, cv = self._cached_layers(c, lws, h, ck0, cv0, 0)
            key, k0 = jax.random.split(key)
            tok = sample(logits_of(h[:, -1]), k0)
            done = (tok == eos)

            def step(carry, i):
                tok, ck, cv, done, key = carry
                pos = T + i
                h = self._embed(c, wte, wpe, tok[:, None], pos)
                h, ck, cv = self._cached_layers(c, lws, h, ck, cv, pos)
                key, ks = jax.random.split(key)
                nxt = sample(logits_of(h[:, -1]), ks)
                nxt = jnp.where(done, jnp.asarray(eos, ids.dtype), nxt)
                done = done | (nxt == eos)
                return (nxt, ck, cv, done, key), tok

            (last, _, _, _, _), toks = jax.lax.scan(
                step, (tok, ck, cv, done, key),
                jnp.arange(max_new_tokens - 1))
            new = jnp.concatenate(
                [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
            return jnp.concatenate([ids, new], axis=1)

        # sampling params only affect the trace when do_sample is on
        cache_key = (B, T, int(max_new_tokens), eos,
                     (bool(do_sample), float(temperature), int(top_k),
                      float(top_p))
                     if do_sample else False)
        # LRU-bounded executable cache: long-running processes seeing many
        # request shapes must not leak compiled programs (the serving
        # engine avoids the per-shape explosion entirely by bucketing)
        from collections import OrderedDict
        jits = getattr(self, "_gen_cache", None)
        if jits is None:
            jits = self._gen_cache = OrderedDict()
        cap = max(1, int(getattr(self, "_gen_cache_max", 16)))
        if cache_key in jits:
            jits.move_to_end(cache_key)
        else:
            while len(jits) >= cap:
                jits.popitem(last=False)  # evict least-recently-used
            jits[cache_key] = jax.jit(run)
        out = jits[cache_key](lws, wte, wpe, lnf_w, lnf_b, head, ids, key)
        return Tensor._wrap(out)

    # -- 1F1B pipeline decomposition ----------------------------------------
    def pipeline_parts(self, tp_axis=None):
        """Split the model for the compiled 1F1B schedule
        (distributed.pipeline.pipeline_value_and_grad): embedding in the
        first stage, final-norm + head + token-sum CE loss in the last —
        mirroring the reference's PipelineLayer partition where
        SharedLayerDesc embeddings and the loss_fn live on the end stages
        (fleet/meta_parallel/parallel_layers/pp_layers.py:56).

        With ``tp_axis`` the stage bodies are MANUAL tensor-parallel over
        that mesh axis (Megatron column/row split with explicit
        copy_to_mp/reduce_from_mp, vocab-parallel embedding + parallel CE) —
        the composition the reference runs as its flagship TP x PP recipe
        (pipeline_parallel.py:459 with mp_layers).  GSPMD cannot place mp
        collectives inside the 1F1B per-stage cond dispatch, hence manual.

        Returns (first_fn, mid_fn, last_fn, stage_params, extras,
        grad_names, specs, grad_fixup): stage_params leaves are
        [pp, L/pp, ...]; extras holds the end-stage weights.  `specs` is
        None or (param_specs, extra_specs) PartitionSpec dicts for
        shard_map; `grad_fixup(name, g)` undoes any weight-layout permutation
        on the returned gradients.  Loss convention: SUM over tokens
        (divide by token count for the mean).
        """
        c = self.config
        pp = hybrid_degrees().get("pp", 1)
        L = c.num_layers
        if L % pp != 0:
            raise ValueError(f"num_layers {L} not divisible by pp {pp}")
        lpp = L // pp
        names = self._stacked()
        eps = c.layer_norm_epsilon
        tie = c.tie_word_embeddings
        use_rope = c.use_rope
        use_dropout = self.training and c.dropout > 0
        moe = c.num_experts > 0

        if tp_axis is not None:
            return self._pipeline_parts_tp(tp_axis, pp, lpp)

        block = self._block_fn(c, self.training, None)
        if use_dropout:
            from ..tensor.random import _next_key
            dkey = _next_key()

        stage_params = {
            n: getattr(self, n)._data.reshape(
                pp, lpp, *getattr(self, n)._data.shape[1:])
            for n in names}
        extras = {"wte": self.wte._data, "lnf_w": self.lnf_w._data,
                  "lnf_b": self.lnf_b._data}
        if not use_rope:
            extras["wpe"] = self.wpe._data
        if not tie:
            extras["head"] = self.lm_head._data

        def first_fn(ex, ids):
            h = jnp.take(ex["wte"], ids, axis=0)
            if not use_rope:
                h = h + jnp.take(ex["wpe"], jnp.arange(ids.shape[1]), axis=0)
            return h

        def mid_fn(sp, h, m=0):
            # per-(microbatch, global layer) dropout keys: fold_in replays
            # identically in the backward/W vjps of the schedule (the
            # reference's RNG replay, fleet/recompute/recompute.py:109)
            stage = jax.lax.axis_index("pp") if pp > 1 else 0

            def body(carry, xs):
                hh, aux_sum = carry
                lw, li = xs
                key = None
                if use_dropout:
                    key = jax.random.fold_in(
                        jax.random.fold_in(dkey, m), stage * lpp + li)
                hh, aux = block(hh, (lw, key))
                if aux is not None:
                    aux_sum = aux_sum + aux
                return (hh, aux_sum), None

            (h, aux), _ = jax.lax.scan(
                body, (h, jnp.zeros((), jnp.float32)),
                (sp, jnp.arange(lpp)))
            return (h, aux * c.moe_aux_weight) if moe else h

        mid_fn.mb_aware = use_dropout
        mid_fn.aux_aware = moe

        def last_fn(ex, h, labels):
            h = _norm(h, ex["lnf_w"], ex["lnf_b"], eps)
            w = ex["wte"].T if tie else ex["head"]
            logits = jnp.matmul(h, w,
                                precision=matmul_precision()).astype(
                                    jnp.float32)
            lse = jax.nn.logsumexp(logits, -1)
            picked = jnp.take_along_axis(
                logits, labels[..., None].astype(jnp.int32), -1)[..., 0]
            return jnp.sum(lse - picked)

        return (first_fn, mid_fn, last_fn, stage_params, extras, names,
                None, None)

    def _pipeline_parts_tp(self, ax, pp, lpp):
        """Manual-TP stage decomposition (see pipeline_parts docstring)."""
        import numpy as np
        from ..distributed.env import get_mesh
        from ..distributed.mp_ops import (copy_to_mp, reduce_from_mp,
                                          vocab_parallel_ce_sum,
                                          vocab_parallel_embedding)
        c = self.config
        if c.num_experts > 0:
            raise NotImplementedError(
                "MoE blocks under the manual-TP 1F1B path are not supported;"
                " use incubate.MoELayer with the GSPMD schedules")
        mesh = get_mesh()
        mp = mesh.shape[ax]
        H, nh, F, V = (c.hidden_size, c.num_heads, c.ffn_hidden_size,
                       c.vocab_size)
        hd = H // nh
        if nh % mp or F % mp or V % mp:
            raise ValueError(
                f"tensor parallel degree {mp} must divide num_heads {nh}, "
                f"ffn_hidden {F} and vocab {V}")
        eps = c.layer_norm_epsilon
        tie = c.tie_word_embeddings
        use_rope = c.use_rope
        use_flash = c.use_flash_attention
        names = self._stacked()

        # The fused qkv weight is laid out q|k|v along its 3H columns;
        # column-sharding that directly would give member j a mixed slice.
        # Permute to shard-major [mp, (q_j|k_j|v_j)] so the LOCAL thirds are
        # q/k/v (the reference shards q, k, v separately inside
        # ColumnParallelLinear for the same reason).
        Hm = H // mp
        perm = np.concatenate([
            np.concatenate([np.arange(j * Hm, (j + 1) * Hm) + t * H
                            for t in range(3)])
            for j in range(mp)])
        inv = np.argsort(perm)

        stage_params = {}
        for n in names:
            a = getattr(self, n)._data
            if n == "qkv_w":
                a = a[:, :, perm]
            elif n == "qkv_b":
                a = a[:, perm]
            stage_params[n] = a.reshape(pp, lpp, *a.shape[1:])
        extras = {"wte": self.wte._data, "lnf_w": self.lnf_w._data,
                  "lnf_b": self.lnf_b._data}
        if not use_rope:
            extras["wpe"] = self.wpe._data
        if not tie:
            extras["head"] = self.lm_head._data

        P_ = P
        param_specs = {
            "ln1_w": P_("pp"), "ln1_b": P_("pp"),
            "qkv_w": P_("pp", None, None, ax),
            "qkv_b": P_("pp", None, ax),
            "proj_w": P_("pp", None, ax, None), "proj_b": P_("pp"),
            "ln2_w": P_("pp"), "ln2_b": P_("pp"),
            "fc1_w": P_("pp", None, None, ax),
            "fc1_b": P_("pp", None, ax),
            "fc2_w": P_("pp", None, ax, None), "fc2_b": P_("pp"),
        }
        extra_specs = {"wte": P_(ax, None), "lnf_w": P_(), "lnf_b": P_()}
        if not use_rope:
            extra_specs["wpe"] = P_()
        if not tie:
            extra_specs["head"] = P_(None, ax)

        use_dropout = self.training and c.dropout > 0
        drop = c.dropout if use_dropout else 0.0
        if use_dropout:
            from ..tensor.random import _next_key
            dkey = _next_key()

        def block_tp(h, lw, key):
            b, s, _ = h.shape
            x = _norm(h, lw["ln1_w"], lw["ln1_b"], eps)
            x = copy_to_mp(x, ax)
            qkv = jnp.matmul(x, lw["qkv_w"],
                             precision=matmul_precision()) + lw["qkv_b"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            nh_loc = q.shape[-1] // hd
            q = q.reshape(b, s, nh_loc, hd)
            k = k.reshape(b, s, nh_loc, hd)
            v = v.reshape(b, s, nh_loc, hd)
            if use_rope:
                from ..kernels.rope import apply_rope
                q = apply_rope(q)
                k = apply_rope(k)
            if use_flash:
                o = flash_attention_fwd(q, k, v, causal=True)
            else:
                o = reference_attention(q, k, v, causal=True)
            o = o.reshape(b, s, nh_loc * hd)
            a = reduce_from_mp(
                jnp.matmul(o, lw["proj_w"], precision=matmul_precision()),
                ax) + lw["proj_b"]
            if drop > 0:
                # key depends only on (microbatch, layer): every mp member
                # draws the SAME mask on the full (post-psum) activation
                key, k1 = jax.random.split(key)
                a = _dropout(a, k1, drop)
            h = h + a
            x = _norm(h, lw["ln2_w"], lw["ln2_b"], eps)
            x = copy_to_mp(x, ax)
            up = jnp.matmul(x, lw["fc1_w"],
                            precision=matmul_precision()) + lw["fc1_b"]
            f = reduce_from_mp(
                jnp.matmul(jax.nn.gelu(up), lw["fc2_w"],
                           precision=matmul_precision()),
                ax) + lw["fc2_b"]
            if drop > 0:
                key, k2 = jax.random.split(key)
                f = _dropout(f, k2, drop)
            return h + f

        def first_fn(ex, ids):
            h = vocab_parallel_embedding(ids, ex["wte"], ax)
            if not use_rope:
                h = h + jnp.take(ex["wpe"], jnp.arange(ids.shape[1]), axis=0)
            return h

        def mid_fn(sp, h, m=0):
            stage = jax.lax.axis_index("pp")

            def body(carry, xs):
                lw, li = xs
                key = None
                if use_dropout:
                    key = jax.random.fold_in(
                        jax.random.fold_in(dkey, m), stage * lpp + li)
                return block_tp(carry, lw, key), None

            h, _ = jax.lax.scan(body, h, (sp, jnp.arange(lpp)))
            return h

        mid_fn.mb_aware = use_dropout

        def last_fn(ex, h, labels):
            hn = _norm(h, ex["lnf_w"], ex["lnf_b"], eps)
            hn = copy_to_mp(hn, ax)
            w = ex["wte"].T if tie else ex["head"]  # local [H, V/mp]
            logits = jnp.matmul(hn, w, precision=matmul_precision())
            return vocab_parallel_ce_sum(logits, labels, ax)

        def grad_fixup(n, g):
            if n == "qkv_w":
                return g[..., inv]
            if n == "qkv_b":
                return g[..., inv]
            return g

        return (first_fn, mid_fn, last_fn, stage_params, extras, names,
                (param_specs, extra_specs), grad_fixup)


class GPTPretrainingCriterion(Layer):
    """Causal-LM loss (reference: paddlenlp GPTPretrainingCriterion —
    ParallelCrossEntropy over vocab-sharded logits)."""

    def __init__(self, config=None):
        super().__init__()

    def forward(self, logits, labels, loss_mask=None):
        def fn(lg, lb, *mask):
            # lse - picked form: identical math to -log_softmax[label], but
            # XLA never materialises the [B,S,V] fp32 log-prob array (the
            # logsumexp reduction and the label gather fuse into the logits
            # producer) — measured ~4% step-time saving at GPT-125M.
            lg = lg.astype(jnp.float32)
            lse = jax.nn.logsumexp(lg, -1)
            picked = jnp.take_along_axis(
                lg, lb[..., None].astype(jnp.int32), -1)[..., 0]
            loss = lse - picked
            if mask:
                m = mask[0].astype(jnp.float32)
                return jnp.sum(loss * m) / jnp.maximum(jnp.sum(m), 1.0)
            return jnp.mean(loss)
        if loss_mask is not None:
            return apply_op("gpt_loss", fn, logits, labels, loss_mask)
        return apply_op("gpt_loss", fn, logits, labels)
