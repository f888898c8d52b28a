"""DistributedTrainStep — the compiled hybrid-parallel training step.

This is the TPU replacement for the reference's entire distributed execution
path: Fleet wrappers + EagerReducer + sharding optimizers + the PIR executor
(SURVEY §3.4).  One jitted XLA program computes forward, backward, and the
optimizer update with:
- parameters/optimizer-state placed per their PartitionSpec annotations
  (TP via mp_layers, FSDP via apply_fsdp_annotations),
- the batch sharded over the data axes,
- GSPMD inserting + overlapping every collective (grad reduce-scatter /
  allreduce, TP psums, stage-3 all-gathers),
- buffer donation so weights update in place (no 2x memory).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.state import STATE
from ..core.tensor import Tensor
from ..jit import (bind_layer_state, bind_optimizer_state, layer_state,
                   optimizer_state, param_positions)
from .env import data_axes, get_mesh


class DistributedTrainStep:
    def __init__(self, model, loss_fn, optimizer, mesh=None, donate=True,
                 batch_spec=None, scaler=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh or get_mesh()
        self._jit = None
        self._struct = None
        self._donate = donate
        self._batch_spec = batch_spec
        self.scaler = scaler if (scaler is not None
                                 and scaler.is_enable()) else None

    # -- sharding helpers ----------------------------------------------------
    def _param_shardings(self):
        assert self.mesh is not None, "build a mesh first (fleet.init)"
        out = {}
        for k, p in self.model.named_parameters():
            spec = p.placements if p.placements is not None else P()
            out[k] = NamedSharding(self.mesh, spec)
        return out

    def _buffer_shardings(self):
        return {k: NamedSharding(self.mesh, P())
                for k, _ in self.model.named_buffers()}

    def _opt_shardings(self, opt_state, param_shardings):
        """Optimizer accumulators inherit their parameter's sharding — or,
        for ZeRO stage 1/2 (params replicated, state sharded: reference
        dygraph_sharding_optimizer.py:44), the param's ``_opt_state_spec``
        recorded by apply_fsdp_annotations(stage<=2)."""
        pos = param_positions(self.optimizer)   # the traced state's keys
        by_pos = {}
        for k, p in self.model.named_parameters():
            if id(p) not in pos:
                continue
            oss = getattr(p, "_opt_state_spec", None)
            by_pos[pos[id(p)]] = (NamedSharding(self.mesh, oss)
                                  if oss is not None else param_shardings[k])
        acc = {}
        for name, store in opt_state["acc"].items():
            acc[name] = {}
            for i, v in store.items():
                if i in by_pos and hasattr(v, "ndim") and v.ndim > 0:
                    acc[name][i] = by_pos[i]
                else:
                    acc[name][i] = NamedSharding(self.mesh, P())
        master = {i: by_pos.get(i, NamedSharding(self.mesh, P()))
                  for i in opt_state["master"]}
        return {"acc": acc, "master": master}

    def _data_sharding(self, x):
        spec = self._batch_spec
        if spec is None:
            spec = P(data_axes())
        nd = getattr(x, "ndim", 0)
        parts = list(spec) + [None] * max(0, nd - len(spec))
        return NamedSharding(self.mesh, P(*parts[:nd] if nd else []))

    # -- compile -------------------------------------------------------------
    def _make_jit(self, params, buffers, opt_state, args_data):
        from ..jit import _scaled_backward, _skip_select
        model, loss_fn, opt = self.model, self.loss_fn, self.optimizer
        mesh = self.mesh
        scaler = self.scaler

        def step_fn(params, buffers, opt_state, lr, rng_key, sstate, args):
            from ..tensor import random as _rnd
            bind_layer_state(model, params, buffers)
            bind_optimizer_state(opt, opt_state)
            prev_lr = opt._learning_rate
            prev_grad = STATE.grad_enabled
            opt._learning_rate = lr
            _rnd._TRACE_CHAIN[0] = _rnd._TraceKeyChain(rng_key)
            STATE.tracing_depth += 1
            try:
                wargs = jax.tree_util.tree_map(
                    lambda x: Tensor._wrap(x) if isinstance(
                        x, (jax.Array, jax.core.Tracer)) else x, args)
                STATE.grad_enabled = True
                loss = loss_fn(model, *wargs)
                if scaler is not None:
                    found = _scaled_backward(model, opt, loss, lr,
                                             sstate["scale"])
                else:
                    loss.backward()
                opt.step()
                opt.clear_grad()
            finally:
                STATE.tracing_depth -= 1
                _rnd._TRACE_CHAIN[0] = None
                opt._learning_rate = prev_lr
                STATE.grad_enabled = prev_grad
            new_params = {k: p._data for k, p in model.named_parameters()}
            new_buffers = {k: b._data for k, b in model.named_buffers()}
            new_opt = optimizer_state(opt)
            if scaler is not None:
                new_params = _skip_select(found, params, new_params)
                new_opt = _skip_select(found, opt_state, new_opt)
                sstate = scaler._traced_update(sstate, found)
            return loss._data, new_params, new_buffers, new_opt, sstate

        pshard = self._param_shardings()
        bshard = self._buffer_shardings()
        oshard_in = self._opt_shardings(opt_state, pshard)
        repl = NamedSharding(mesh, P())
        args_shard = jax.tree_util.tree_map(self._data_sharding, args_data)
        in_shardings = (pshard, bshard, oshard_in, repl, repl, repl,
                        args_shard)

        # The output opt-state structure may be larger than the input one
        # (accumulators are created lazily on the first step) — discover it
        # with eval_shape, then restore the live objects.
        lr0 = jnp.zeros((), jnp.float32)
        key0 = jax.random.key(0)
        sstate0 = scaler._traced_state() if scaler is not None else {}
        with mesh:
            out_struct = jax.eval_shape(step_fn, params, buffers, opt_state,
                                        lr0, key0, sstate0, args_data)
        bind_layer_state(self.model, params, buffers)
        bind_optimizer_state(self.optimizer, opt_state)
        oshard_out = self._opt_shardings(
            {"acc": out_struct[3]["acc"], "master": out_struct[3]["master"]},
            pshard)
        out_shardings = (repl, pshard, bshard, oshard_out, repl)
        donate = ()
        if self._donate:
            donate = (1,) if scaler is not None else (0, 1, 2)
        return jax.jit(step_fn,
                       in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       donate_argnums=donate)

    def __call__(self, *args):
        params, buffers = layer_state(self.model)
        opt_state = optimizer_state(self.optimizer)
        args_data = jax.tree_util.tree_map(
            lambda x: x._data if isinstance(x, Tensor) else x, args,
            is_leaf=lambda x: isinstance(x, Tensor))
        struct = jax.tree_util.tree_structure(opt_state)
        if self._jit is None or struct != self._struct:
            self._jit = self._make_jit(params, buffers, opt_state, args_data)
            self._struct = struct
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        from ..tensor.random import _DEFAULT_GEN
        rng_key = _DEFAULT_GEN.next_key()
        self.optimizer._step_count += 1
        sstate = (self.scaler._traced_state() if self.scaler is not None
                  else {})
        with self.mesh:
            loss, new_params, new_buffers, new_opt, new_sstate = self._jit(
                params, buffers, opt_state, lr, rng_key, sstate, args_data)
        bind_layer_state(self.model, new_params, new_buffers)
        bind_optimizer_state(self.optimizer, new_opt)
        if self.scaler is not None:
            self.scaler._absorb(new_sstate)
        from .elastic import heartbeat
        heartbeat()  # no-op unless under the elastic launcher
        return Tensor._wrap(loss)


class Pipeline1F1BTrainStep(DistributedTrainStep):
    """Compiled train step using the 1F1B pipeline schedule
    (pipeline.pipeline_value_and_grad) instead of tape backward.

    Reference analogue: PipelineParallel.train_batch →
    forward_backward_pipeline (fleet/meta_parallel/pipeline_parallel.py:697,
    459).  The model must provide `pipeline_parts()` (see
    models/gpt.py:GPTForCausalLM.pipeline_parts).  Gradients flow straight
    from the schedule into param.grad, then the wrapped optimizer runs — the
    activation footprint is O(pp) microbatches per stage vs O(M) for
    jax.grad through the GPipe scan.
    """

    def __init__(self, model, optimizer, num_microbatches=None, mesh=None,
                 donate=True, batch_spec=None, schedule="1f1b"):
        super().__init__(model, loss_fn=None, optimizer=optimizer, mesh=mesh,
                         donate=donate, batch_spec=batch_spec)
        self.num_microbatches = num_microbatches
        if schedule not in ("1f1b", "zero_bubble"):
            raise ValueError(f"unknown pipeline schedule {schedule!r}")
        self.schedule = schedule

    def _make_jit(self, params, buffers, opt_state, args_data):
        from .pipeline import pipeline_value_and_grad
        model, opt = self.model, self.optimizer
        mesh = self.mesh
        pp = mesh.shape["pp"]
        if mesh.shape.get("sep", 1) > 1:
            raise NotImplementedError(
                "Pipeline1F1BTrainStep does not compose with sep>1 yet; "
                "use pp_schedule='gpipe' with ring attention for long "
                "sequences")
        # mp > 1 runs the manual-TP stage body (model._pipeline_parts_tp):
        # Megatron column/row splits with explicit psum('mp'), vocab-parallel
        # embedding and parallel CE — GSPMD collectives cannot live in the
        # 1F1B per-stage cond dispatch, manual ones can because every mp
        # member of a stage branches identically.
        tp_axis = "mp" if mesh.shape.get("mp", 1) > 1 else None
        ids0, _ = args_data
        M = self.num_microbatches or max(2 * pp, 1)
        dp = mesh.shape.get("dp", 1) * mesh.shape.get("sharding", 1)
        # each microbatch must still shard over the data axes — otherwise
        # GSPMD reshards inside the schedule's conds (rendezvous deadlock)
        while M > 1 and (ids0.shape[0] % M != 0
                         or (ids0.shape[0] // M) % dp != 0):
            M -= 1

        def step_fn(params, buffers, opt_state, lr, rng_key, sstate, args):
            from ..tensor import random as _rnd
            ids, labels = args
            bind_layer_state(model, params, buffers)
            bind_optimizer_state(opt, opt_state)
            prev_lr = opt._learning_rate
            opt._learning_rate = lr
            # thread the step's rng key (dropout keys derive from it via
            # fold_in inside pipeline_parts); without this, _next_key()
            # would split the GLOBAL generator inside the trace and leak a
            # tracer into it
            _rnd._TRACE_CHAIN[0] = _rnd._TraceKeyChain(rng_key)
            STATE.tracing_depth += 1
            try:
                first_fn, mid_fn, last_fn, sp, ex, names, specs, fixup = \
                    model.pipeline_parts(tp_axis=tp_axis)
                pspecs, especs = specs if specs is not None else (None, None)
                # aux (MoE gate loss, pre-weighted in mid_fn) enters the
                # schedule loss as aux * tokens/M so the /tokens below
                # yields weight * mean-per-microbatch aux
                aux_scale = (ids.size / M
                             if getattr(mid_fn, "aux_aware", False) else None)
                loss_sum, dsp, dex = pipeline_value_and_grad(
                    first_fn, mid_fn, last_fn, sp, ex, ids, labels, M,
                    mesh=mesh, param_specs=pspecs, extra_specs=especs,
                    manual_axes=("pp", tp_axis) if tp_axis else ("pp",),
                    schedule=self.schedule, aux_scale=aux_scale)
                ntok = jnp.asarray(ids.size, jnp.float32)
                loss = loss_sum / ntok
                by_name = dict(model.named_parameters())
                for n in names:
                    p = by_name[n]
                    g = dsp[n]
                    if fixup is not None:
                        g = fixup(n, g)
                    g = g.reshape(p._data.shape) / ntok
                    p.grad = Tensor._wrap(g.astype(p._data.dtype))
                for key, pname in (("wte", "wte"), ("lnf_w", "lnf_w"),
                                   ("lnf_b", "lnf_b"), ("wpe", "wpe"),
                                   ("head", "lm_head")):
                    if key in dex and pname in by_name:
                        p = by_name[pname]
                        p.grad = Tensor._wrap(
                            (dex[key] / ntok).astype(p._data.dtype))
                opt.step()
                opt.clear_grad()
            finally:
                STATE.tracing_depth -= 1
                _rnd._TRACE_CHAIN[0] = None
                opt._learning_rate = prev_lr
            new_params = {k: p._data for k, p in model.named_parameters()}
            new_buffers = {k: b._data for k, b in model.named_buffers()}
            return loss, new_params, new_buffers, optimizer_state(opt), sstate

        pshard = self._param_shardings()
        bshard = self._buffer_shardings()
        oshard_in = self._opt_shardings(opt_state, pshard)
        repl = NamedSharding(mesh, P())
        args_shard = jax.tree_util.tree_map(self._data_sharding, args_data)
        in_shardings = (pshard, bshard, oshard_in, repl, repl, repl,
                        args_shard)
        lr0 = jnp.zeros((), jnp.float32)
        key0 = jax.random.key(0)
        with mesh:
            out_struct = jax.eval_shape(step_fn, params, buffers, opt_state,
                                        lr0, key0, {}, args_data)
        bind_layer_state(self.model, params, buffers)
        bind_optimizer_state(self.optimizer, opt_state)
        oshard_out = self._opt_shardings(
            {"acc": out_struct[3]["acc"], "master": out_struct[3]["master"]},
            pshard)
        out_shardings = (repl, pshard, bshard, oshard_out, repl)
        return jax.jit(step_fn,
                       in_shardings=in_shardings,
                       out_shardings=out_shardings,
                       donate_argnums=(0, 1, 2) if self._donate else ())


class DistributedEvalStep:
    """Compiled forward-only step with the same shardings."""

    def __init__(self, model, fn=None, mesh=None, batch_spec=None):
        self.model = model
        self.fn = fn
        self.mesh = mesh or get_mesh()
        self._jit = None
        self._batch_spec = batch_spec

    def __call__(self, *args):
        model = self.model
        params, buffers = layer_state(model)
        args_data = jax.tree_util.tree_map(
            lambda x: x._data if isinstance(x, Tensor) else x, args,
            is_leaf=lambda x: isinstance(x, Tensor))
        if self._jit is None:
            fn = self.fn

            def eval_fn(params, buffers, args):
                bind_layer_state(model, params, buffers)
                wargs = jax.tree_util.tree_map(
                    lambda x: Tensor._wrap(x) if isinstance(
                        x, (jax.Array, jax.core.Tracer)) else x, args)
                from ..core.state import no_grad_guard
                with no_grad_guard():
                    out = (fn(model, *wargs) if fn is not None
                           else model(*wargs))
                return jax.tree_util.tree_map(
                    lambda t: t._data if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda t: isinstance(t, Tensor))
            self._jit = jax.jit(eval_fn)
        with self.mesh:
            out = self._jit(params, buffers, args_data)
        return jax.tree_util.tree_map(
            lambda x: Tensor._wrap(x) if isinstance(x, jax.Array) else x, out)
