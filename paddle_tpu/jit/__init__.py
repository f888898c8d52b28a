"""paddle_tpu.jit — whole-program capture.

Reference analogue: paddle.jit (dy2static AST transpile + SOT bytecode capture,
python/paddle/jit/ — 33k LoC) feeding PIR + CINN.

TPU-native redesign: the eager layer executes jnp calls on ``Tensor._data``;
under ``jax.jit`` those same calls trace symbolically, so "dynamic-to-static"
needs no AST rewriting or frame-eval hook — ``to_static`` simply
functionalizes a Layer (parameters/buffers become pytree inputs, mutated
buffers become outputs) and hands the python callable to ``jax.jit``.  The
autograd tape also traces, so an entire train step (forward + backward +
optimizer update) compiles into ONE XLA program — the analogue of the
reference's static-graph executor running a whole Program, with XLA playing
CINN's role.  Guards/retrace are keyed by jax's abstract signature
(shape/dtype/pytree), matching SOT guard semantics.
"""

from __future__ import annotations

import contextlib
import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as _P

from ..analysis import program_audit as _audit
from ..core import flags as _flags
from ..core.state import STATE, no_grad_guard
from ..core.tensor import Parameter, Tensor
from ..profiler import counters as _counters
from ..profiler import devicetime as _devicetime
from ..profiler import flight as _flight
from ..profiler import host_tracer as _trace
from ..profiler import metrics as _metrics


def _is_layer(obj):
    from ..nn.layer.layers import Layer
    return isinstance(obj, Layer)


# ---------------------------------------------------------------------------
# State (de)hydration: Layer/Optimizer <-> pytree of jax arrays
#
# The jit.host.* counters (profiler.counters) tally every hydrate/bind that
# runs as eager host work (trace-time binds inside jax.jit are one-time
# compile cost and excluded), so the perf contract of CompiledTrainStep
# ("zero per-parameter host work in steady state") is checkable:
# scripts/bench_smoke.py and scripts/check_counters.py snapshot the registry
# around steady-state steps and assert no movement.
# ---------------------------------------------------------------------------
_HOST_SYNC_KEYS = ("layer_state", "bind_layer_state", "optimizer_state",
                   "bind_optimizer_state")


def host_sync_counts():
    """Hydrate/bind call counters, as a plain dict (back-compat view over
    the jit.host.* entries of profiler.counters)."""
    return {k: _counters.get("jit.host." + k) for k in _HOST_SYNC_KEYS}


def layer_state(layer):
    if STATE.tracing_depth == 0:
        _counters.inc("jit.host.layer_state")
    params = {k: p._data for k, p in layer.named_parameters()}
    buffers = {k: b._data for k, b in layer.named_buffers()}
    return params, buffers


def bind_layer_state(layer, params, buffers):
    if STATE.tracing_depth == 0:
        _counters.inc("jit.host.bind_layer_state")
    for k, p in layer.named_parameters():
        if k in params:
            p._data = params[k]
    for k, b in layer.named_buffers():
        if k in buffers:
            b._data = buffers[k]


def param_positions(opt):
    """``{id(param): position in the optimizer's parameter list}``.

    The optimizer keys its accumulator stores by ``id(p)``, and an address
    differs in every process.  Carried through ``jit`` as dict keys it
    would order the state pytree — hence the program's parameters, its HLO
    and its persistent-compile-cache key — differently on every run, so a
    train step would never be found in the cache again.  The traced state
    is therefore keyed by POSITION (:func:`optimizer_state` /
    :func:`bind_optimizer_state` translate at the boundary)."""
    return {id(p): i for i, p in enumerate(opt._parameter_list or [])
            if p is not None}


def optimizer_state(opt):
    """The optimizer's accumulators / master weights as a jit-able pytree,
    keyed by parameter position (see :func:`param_positions`)."""
    if STATE.tracing_depth == 0:
        _counters.inc("jit.host.optimizer_state")
    pos = param_positions(opt)
    accs = {name: {pos[pid]: v for pid, v in store.items()}
            for name, store in opt._accumulators.items()}
    masters = {pos[pid]: v for pid, v in opt._master_weights.items()}
    return {"acc": accs, "master": masters}


def bind_optimizer_state(opt, state):
    """Inverse of :func:`optimizer_state`: position keys back to the
    ``id(p)`` keys the optimizer's update rules look up."""
    if STATE.tracing_depth == 0:
        _counters.inc("jit.host.bind_optimizer_state")
    params = opt._parameter_list
    opt._accumulators = {name: {id(params[i]): v for i, v in store.items()}
                         for name, store in state["acc"].items()}
    opt._master_weights = {id(params[i]): v
                           for i, v in state["master"].items()}


class StaticFunction:
    """Compiled wrapper over a python function or Layer.forward
    (reference analogue: jit/dy2static/program_translator.py:321
    StaticFunction)."""

    def __init__(self, fn, layer=None, build_strategy=None,
                 full_graph=True, backend=None, input_spec=None):
        self._fn = fn
        self._layer = layer
        self._cache = {}
        functools.update_wrapper(self, fn)

    def _compiled(self, train_flag):
        if train_flag in self._cache:
            return self._cache[train_flag]

        def runner(params, buffers, args, kwargs):
            _counters.inc("jit.traces")  # body runs as python only per trace
            if self._layer is not None:
                bind_layer_state(self._layer, params, buffers)
            wargs = jax.tree_util.tree_map(
                lambda x: Tensor._wrap(x) if isinstance(
                    x, (jax.Array, jax.core.Tracer)) else x, args)
            wkwargs = jax.tree_util.tree_map(
                lambda x: Tensor._wrap(x) if isinstance(
                    x, (jax.Array, jax.core.Tracer)) else x, kwargs)
            STATE.tracing_depth += 1
            try:
                with no_grad_guard():
                    out = self._fn(*wargs, **wkwargs)
            finally:
                STATE.tracing_depth -= 1
            out_data = jax.tree_util.tree_map(
                lambda x: x._data if isinstance(x, Tensor) else x, out,
                is_leaf=lambda x: isinstance(x, Tensor))
            new_buffers = ({k: b._data for k, b in
                            self._layer.named_buffers()}
                           if self._layer is not None else {})
            return out_data, new_buffers

        jitted = jax.jit(runner)
        self._cache[train_flag] = jitted
        return jitted

    def __call__(self, *args, **kwargs):
        with _trace.span("jit.static_function"):
            params, buffers = (layer_state(self._layer)
                               if self._layer is not None else ({}, {}))
            args_data = jax.tree_util.tree_map(
                lambda x: x._data if isinstance(x, Tensor) else x, args,
                is_leaf=lambda x: isinstance(x, Tensor))
            kwargs_data = jax.tree_util.tree_map(
                lambda x: x._data if isinstance(x, Tensor) else x, kwargs,
                is_leaf=lambda x: isinstance(x, Tensor))
            training = (self._layer.training if self._layer is not None
                        else False)
            traces_before = _counters.get("jit.traces")
            out_data, new_buffers = self._compiled(training)(
                params, buffers, args_data, kwargs_data)
            _counters.inc("jit.cache_hits"
                          if _counters.get("jit.traces") == traces_before
                          else "jit.cache_misses")
            if self._layer is not None:
                for k, b in self._layer.named_buffers():
                    if k in new_buffers:
                        b._data = new_buffers[k]
            return jax.tree_util.tree_map(
                lambda x: Tensor._wrap(x) if isinstance(x, jax.Array) else x,
                out_data)

    @property
    def code(self):
        import inspect
        try:
            return inspect.getsource(self._fn)
        except OSError:
            return "<source unavailable>"

    def concrete_program(self):
        return None


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=True):
    """paddle.jit.to_static (reference: jit/api.py to_static)."""
    def decorate(obj):
        if _is_layer(obj):
            obj.forward = StaticFunction(obj.forward, layer=obj)
            return obj
        if hasattr(obj, "__self__") and _is_layer(obj.__self__):
            return StaticFunction(obj, layer=obj.__self__)
        return StaticFunction(obj)
    if function is None:
        return decorate
    return decorate(function)


def not_to_static(fn):
    fn._not_to_static = True
    return fn


class ignore_module:
    def __init__(self, modules):
        pass


# ---------------------------------------------------------------------------
# Traced dynamic loss scaling inside the one-program train step.
#
# Reference analogue: GradScaler.step (amp/grad_scaler.py:619) — unscale,
# cross-rank found-inf reduction, conditional optimizer step, scale update.
# Here the whole sequence is part of the XLA program: found_inf is a traced
# scalar; the "skip" is realised by (a) zeroing the gradients and the lr so
# lazily-created accumulators (fp32 master weights, moments) keep their init
# values, and (b) selecting the pre-step value for every state leaf that
# existed before the update.
# ---------------------------------------------------------------------------
def _scaled_backward(model, opt, loss, lr, scale):
    """Scaled backward + in-graph unscale.  Returns found_inf (traced bool)
    and sets opt lr to 0 on overflow so the update is a no-op."""
    (loss * Tensor._wrap(scale.astype(loss._data.dtype))).backward()
    inv = 1.0 / scale
    found = jnp.zeros((), jnp.bool_)
    grads = []
    for _, p in model.named_parameters():
        if p.grad is not None:
            g32 = p.grad._data.astype(jnp.float32) * inv
            found = found | jnp.any(~jnp.isfinite(g32))
            grads.append((p, g32))
    for p, g32 in grads:
        safe = jnp.where(found, jnp.zeros_like(g32), g32)
        p.grad._data = safe.astype(p.grad._data.dtype)
    opt._learning_rate = jnp.where(found, jnp.zeros_like(lr), lr)
    return found


def _skip_select(found, old, new):
    """Leaf-wise jnp.where(found, old, new) over (possibly nested) dicts;
    leaves with no pre-step counterpart keep their new (= init) value."""
    if isinstance(new, dict):
        return {k: _skip_select(found,
                                old.get(k) if isinstance(old, dict) else None,
                                v)
                for k, v in new.items()}
    if old is None or not hasattr(new, "dtype"):
        return new
    return jnp.where(found, old, new)


class CompiledTrainStep:
    """One-XLA-program train step: forward + tape backward + optimizer update,
    compiled together with parameter/optimizer-state donation.

    This is the TPU replacement for the reference's whole static-graph
    training path (Program + StandaloneExecutor + fused optimizer ops,
    SURVEY §3.3) and the primary perf surface of the framework.

    Device-resident state: the flat params/buffers/opt-state pytree lives on
    device between steps — each call feeds the previous call's OUTPUT arrays
    straight back in (donation makes the round trip zero-copy), so the
    steady-state path does ZERO per-parameter python work: no Layer/Optimizer
    dict rebuilds, no rebinds, no per-step lr upload (the device scalar is
    cached against the scheduler's host float), no host RNG (the PRNG key is
    split in-graph and carried).  The python ``model``/``optimizer`` objects
    are therefore stale between steps; they re-converge via:

      * ``step.sync()`` — explicit flush device -> host (cheap, pointer
        rebinds only);
      * automatically before ``model.state_dict()`` /
        ``optimizer.state_dict()`` (checkpointing sees fresh values);
      * automatically when an official mutation API runs
        (``Parameter.set_value``, ``set_state_dict``, ``Layer.to(dtype)``,
        ``amp.decorate``, ``Tensor.zero_`` ...): the mutation barrier in
        ``core.state.bump_param_version`` flushes first, then the next call
        re-hydrates from host so the mutation takes effect.

    Raw ``tensor._data = ...`` pokes are NOT tracked — call
    ``step.invalidate()`` after such surgery.

    Fused multi-step dispatch: with ``fused_steps=K`` (default from
    ``FLAGS_fused_steps``) a whole K-step window compiles into ONE donated
    XLA program — ``jax.lax.scan`` over the single-step body, carry =
    (params, buffers, opt_state, scaler_state, rng_key), xs = the K-stacked
    batch pytree plus the K-vector of learning rates previewed from the
    host scheduler (``LRScheduler.peek``), ys = the per-step lazy losses.
    This amortizes per-step python dispatch/argument handling across K
    steps (the scheduling-overhead analogue of the reference's
    new_executor + CINN fusion) — the lever for short-step (small-model)
    MFU.  Feed windows via ``io.StackingPrefetcher``::

        step = CompiledTrainStep(model, loss_fn, opt, fused_steps=4)
        for w in io.StackingPrefetcher(loader, k=4):
            losses = step(*w)          # ONE dispatch, shape-[k] lazy loss

    Window semantics:

      * a window call returns the K-vector of losses (lazy; materializes on
        ``.numpy()``, which syncs the whole window);
      * ``jit.steps`` / ``optimizer._step_count`` advance by K per window;
        ``jit.host.dispatches`` advances by 1 (the counter gate is
        ``dispatches == steps / K`` in steady state);
      * GradScaler skip-steps, in-graph dropout key splitting and
        ``FLAGS_check_nan_inf`` all run per scan iteration — trajectories
        are bit-identical to K single-step dispatches, and a nan/inf raise
        names the offending step index inside the window;
      * partial windows (tail of a loader whose length is not a multiple of
        K) and the very first window (optimizer accumulators not yet
        materialized, so the scan carry structure is unknown) fall back to
        K single-step dispatches — no batch is dropped or padded;
      * ``.sync()`` and the mutation barrier land on post-window values.

    Telemetry: ``metrics=MetricsLogger(...)`` (profiler.metrics) records
    per-step loss / grad global-norm / lr / scaler scale+skip / step-time /
    tok/s / MFU.  The device-derived scalars are traced into the step
    program and accumulated in a donated on-device accumulator (part of
    the fused-window scan carry); the host harvests them only at existing
    sync boundaries (``sync()``, checkpoint export, or an explicit
    ``metrics_flush()``) — steady-state counter gates (0 retraces /
    hydrates / binds, dispatches == steps/K) hold with metrics ON, which
    ``scripts/check_counters.py`` enforces.

    With ``scaler`` (an enabled amp.GradScaler), fp16 dynamic loss scaling
    runs in-graph: scaled backward, traced found-inf, skipped update, scale
    adjustment — zero host round-trips (reference: amp/grad_scaler.py:619).
    Donation stays full (params/buffers/opt-state) even with the scaler: the
    skip-select reads the pre-step values INSIDE the program, so XLA aliasing
    of inputs to outputs remains legal.

    Multi-chip SPMD: pass ``mesh`` (a ``jax.sharding.Mesh``) to make the
    step mesh-native — every leaf of the donated carry (params, buffers,
    optimizer accumulators/master weights, GradScaler state, RNG chain) is
    placed with a ``NamedSharding`` at hydrate time and its output sharding
    is pinned inside the traced program, so input/output layouts match and
    donation, the retrace budget, and the zero-host-sync steady state hold
    UNCHANGED on the mesh path (same counter gates).  Per-leaf specs
    resolve as: ``shard_rules`` (ordered ``(regex, PartitionSpec)`` pairs
    matched on the parameter/buffer name, see
    ``distributed.sharding_utils.infer_partition_specs``) > the
    PartitionSpec recorded by ``annotate_param`` (model-declared TP
    placements, e.g. GPT's qkv/mlp ``"mp"`` splits) > replicated.  The
    batch dimension of the step args is constrained onto ``batch_axes``
    (default: every data-ish mesh axis — ``dp``/``sharding`` — of size >
    1), which makes GSPMD insert the gradient all-reduce automatically:
    dp=N training is N shards of the global batch with psum'd grads, and a
    1-device mesh is bit-identical to the single-device path.
    """

    def __init__(self, model, loss_fn, optimizer, scaler=None, donate=True,
                 fused_steps=None, mesh=None, shard_rules=None,
                 batch_axes=None, metrics=None):
        import weakref
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # per-step train telemetry (profiler.metrics.MetricsLogger): the
        # device-derived scalars (loss / grad global-norm / scaler state)
        # accumulate INSIDE the donated carry and per-dispatch lazy refs,
        # harvested only at sync boundaries — metrics ON adds zero
        # syncs/retraces/dispatches (gated in scripts/check_counters.py)
        self.metrics = (_metrics.MetricsLogger() if metrics is True
                        else metrics)
        self._macc = None            # donated device metric accumulator
        self._pending = []           # un-harvested per-dispatch metric refs
        self._pending_cap = 512      # auto-harvest backstop
        self._last_dispatch_t = None
        self._tokens_per_step = None
        self._tok_cached = False
        self._n_params = None
        self.scaler = scaler if (scaler is not None
                                 and scaler.is_enable()) else None
        if fused_steps is None:
            fused_steps = int(_flags.flag("FLAGS_fused_steps"))
        if int(fused_steps) < 1:
            raise ValueError(f"fused_steps must be >= 1, got {fused_steps}")
        self.fused_steps = int(fused_steps)
        # keyed by the FLAGS_check_nan_inf value the program was traced
        # under: the guard's finite-ness checks are part of the XLA program,
        # so flag-off runs execute a program with zero check overhead
        self._jits = {}
        # fused window programs, keyed by (check_nan_inf, window length)
        self._fused_jits = {}
        self._donate = donate
        # (params, buffers, opt_state, sstate, rng_carry) — device resident
        self._state = None
        self._seen_version = -1
        self._synced = True
        self._lr_host = None
        self._lr_dev = None
        self._lrs_host = None  # lr vector of the last fused window
        self._lrs_dev = None
        self.mesh = mesh
        if mesh is not None:
            self._init_mesh(shard_rules, batch_axes)
        # state_dict() on the model/optimizer/scaler auto-syncs through this
        model.__dict__["_train_step_owner"] = weakref.ref(self)
        optimizer.__dict__["_train_step_owner"] = weakref.ref(self)
        if self.scaler is not None:
            self.scaler.__dict__["_train_step_owner"] = weakref.ref(self)
        from ..core.state import register_param_sync_hook
        register_param_sync_hook(self.sync)

    # -- mesh plumbing -------------------------------------------------------
    def _init_mesh(self, shard_rules, batch_axes):
        """Resolve one PartitionSpec per carry leaf.  Precedence per
        parameter/buffer name: ``shard_rules`` regex > ``annotate_param``
        placements > replicated; optimizer accumulators and master weights
        inherit their parameter's spec (matched by position in the
        optimizer's parameter list, the traced state's key)."""
        from ..distributed.sharding_utils import (infer_partition_specs,
                                                  validate_spec)
        mesh = self.mesh
        self._rep = NamedSharding(mesh, _P())
        if batch_axes is None:
            from ..distributed.env import DATA_AXES
            batch_axes = tuple(a for a in DATA_AXES
                               if a in mesh.shape and mesh.shape[a] > 1)
        elif isinstance(batch_axes, str):
            batch_axes = (batch_axes,)
        self._batch_axes = tuple(batch_axes)
        div = 1
        for a in self._batch_axes:
            div *= mesh.shape[a]
        self._batch_div = div
        self._batch_spec = (_P(self._batch_axes if len(self._batch_axes) > 1
                               else self._batch_axes[0])
                            if self._batch_axes else None)
        named_p = list(self.model.named_parameters())
        named_b = list(self.model.named_buffers())
        flat = {k: p._data for k, p in named_p}
        flat.update({k: b._data for k, b in named_b})
        ruled = infer_partition_specs(flat, mesh, shard_rules or (),
                                      default=None)
        self._param_specs, self._buffer_specs, self._bypos = {}, {}, {}
        pos = param_positions(self.optimizer)
        for k, p in named_p:
            spec = ruled[k]
            if spec is None:
                placed = getattr(p, "placements", None)
                spec = validate_spec(placed, p._data.shape, mesh, name=k,
                                     quiet=placed is None)
            self._param_specs[k] = spec
            if id(p) in pos:
                self._bypos[pos[id(p)]] = spec
        for k, b in named_b:
            spec = ruled[k]
            if spec is None:
                spec = validate_spec(getattr(b, "placements", None),
                                     b._data.shape, mesh, name=k, quiet=True)
            self._buffer_specs[k] = spec

    def _fit_spec(self, spec, shape):
        """Quiet shape-compatibility filter used inside traced code — a
        param-shaped spec applied to a scalar accumulator (beta pows, ...)
        degrades to replicated without warning spam."""
        from ..distributed.sharding_utils import validate_spec
        return validate_spec(spec, shape, self.mesh, quiet=True)

    def _pin(self, x, spec):
        """with_sharding_constraint a traced carry leaf to its resolved
        spec — pinning every OUTPUT leaf to the same sharding its input was
        hydrated with keeps donation aliasing legal and the program cache
        stable (no propagation-chosen layout drift => no retraces)."""
        if not hasattr(x, "shape"):
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self._fit_spec(spec, x.shape)))

    def _pin_carry(self, new_params, new_buffers, new_opt):
        new_params = {k: self._pin(v, self._param_specs.get(k))
                      for k, v in new_params.items()}
        new_buffers = {k: self._pin(v, self._buffer_specs.get(k))
                       for k, v in new_buffers.items()}
        new_opt = {
            "acc": {an: {i: self._pin(v, self._bypos.get(i))
                         for i, v in store.items()}
                    for an, store in new_opt["acc"].items()},
            "master": {i: self._pin(v, self._bypos.get(i))
                       for i, v in new_opt["master"].items()}}
        return new_params, new_buffers, new_opt

    def _constrain_batch(self, args):
        """Pin the leading (batch) axis of every compatible array leaf of
        the step args to the data-parallel mesh axes, inside the traced
        program — GSPMD then runs the forward/backward on batch shards and
        inserts the gradient all-reduce."""
        if self._batch_spec is None:
            return args
        sharding = NamedSharding(self.mesh, self._batch_spec)

        def pin(x):
            shape = getattr(x, "shape", None)
            if (shape is None or len(shape) < 1
                    or shape[0] % self._batch_div != 0):
                return x
            return jax.lax.with_sharding_constraint(x, sharding)

        return jax.tree_util.tree_map(pin, args)

    def _mesh_put(self, x, spec):
        """Sharded ``device_put`` of one state leaf onto the mesh (hydrate/
        warmup path only, never steady state)."""
        if not hasattr(x, "shape"):
            return x
        sharding = NamedSharding(self.mesh, self._fit_spec(spec, x.shape))
        if isinstance(x, jax.Array) and x.sharding == sharding:
            return x
        out = jax.device_put(x, sharding)
        _counters.inc("dist.device_put_sharded_bytes",
                      int(getattr(out, "nbytes", 0) or 0))
        return out

    def _place_mesh_state(self):
        """Place the freshly-hydrated state tuple onto the mesh: params and
        buffers per their resolved specs, optimizer accumulators / master
        weights like their parameter, GradScaler state and the RNG carry
        replicated."""
        params, buffers, opt_state, sstate, key = self._state
        params = {k: self._mesh_put(v, self._param_specs.get(k))
                  for k, v in params.items()}
        buffers = {k: self._mesh_put(v, self._buffer_specs.get(k))
                   for k, v in buffers.items()}
        opt_state = {
            "acc": {an: {i: self._mesh_put(v, self._bypos.get(i))
                         for i, v in store.items()}
                    for an, store in opt_state["acc"].items()},
            "master": {i: self._mesh_put(v, self._bypos.get(i))
                       for i, v in opt_state["master"].items()}}
        sstate = jax.tree_util.tree_map(
            lambda v: self._mesh_put(v, None), sstate)
        key = jax.device_put(key, self._rep)
        self._state = (params, buffers, opt_state, sstate, key)

    # -- host <-> device state management -----------------------------------
    def _hydrate(self):
        """Read the python objects into the device-resident state tuple."""
        from ..core.state import param_version
        from ..tensor.random import _DEFAULT_GEN
        with _trace.span("jit.hydrate"):
            _counters.inc("jit.hydrates")
            params, buffers = layer_state(self.model)
            opt_state = optimizer_state(self.optimizer)
            sstate = (self.scaler._traced_state() if self.scaler is not None
                      else {})
            self._state = (params, buffers, opt_state, sstate,
                           _DEFAULT_GEN.next_key())
            self._seen_version = param_version()
            self._synced = True
            if self.mesh is not None:
                self._place_mesh_state()

    def sync(self):
        """Flush the device-resident state back into the python
        model/optimizer/scaler objects (pointer rebinds, no host transfer).
        An existing sync boundary is also where pending train metrics are
        harvested into the MetricsLogger (no extra ``jit.syncs``)."""
        if self.metrics is not None:
            self.metrics_flush()
        if self._state is None or self._synced:
            return
        with _trace.span("jit.sync"):
            _counters.inc("jit.syncs")
            params, buffers, opt_state, sstate, _ = self._state
            bind_layer_state(self.model, params, buffers)
            bind_optimizer_state(self.optimizer, opt_state)
            if self.scaler is not None:
                self.scaler._absorb(sstate)
            self._synced = True

    def invalidate(self):
        """Drop the device-resident state; the next call re-hydrates from the
        python objects.  Use after untracked ``t._data = ...`` surgery."""
        self.sync()
        self._state = None

    def export_resume_state(self):
        """Checkpoint hook (``resilience.CheckpointManager``): converge the
        python model/optimizer/scaler objects with the device-resident state
        via ONE counter-gated :meth:`sync`, and return the in-graph RNG
        carry key as raw key data (uint32 ndarray) so an exact-resume
        restore can continue the per-dispatch key chain bit-identically."""
        import numpy as np
        self._ensure_state()
        self.sync()
        return np.array(jax.random.key_data(self._state[4]), copy=True)

    def restore_resume_state(self, rng_carry=None):
        """Rebuild the device-resident state from the (just restored) python
        model/optimizer/scaler objects and install the saved RNG carry key.

        The re-hydrate draws (and discards) one key from the global
        generator, so callers restoring ``paddle.get_rng_state()`` must do
        so AFTER this call for bit-identical resume.  The lr dispatch
        caches are reset so the first resumed dispatch re-reads the
        (restored) scheduler."""
        self._state = None
        self._hydrate()
        if rng_carry is not None:
            params, buffers, opt_state, sstate, _ = self._state
            key = jax.random.wrap_key_data(
                jnp.asarray(rng_carry, jnp.uint32))
            if self.mesh is not None:
                key = jax.device_put(key, self._rep)
            self._state = (params, buffers, opt_state, sstate, key)
        self._lr_host = self._lr_dev = None
        self._lrs_host = self._lrs_dev = None
        # the restored run starts a fresh metric accumulator; un-harvested
        # refs from the faulted timeline are dropped (the flight recorder
        # already captured them at dump time)
        self._macc = None
        self._pending = []
        self._last_dispatch_t = None

    def _step_body(self, check_nan_inf, metrics_on, params, buffers,
                   opt_state, lr, rng_key, sstate, args):
        """One training step as a pure traceable function — the body shared
        by the single-step program and each ``lax.scan`` iteration of a
        fused window.  Returns (loss, params', buffers', opt_state',
        sstate', rng_carry', checks, mets); ``mets`` carries the traced
        per-step telemetry scalars (grad global-norm, scaler scale/skip)
        when ``metrics_on``, else is empty."""
        model, loss_fn, opt = self.model, self.loss_fn, self.optimizer
        scaler = self.scaler
        from ..tensor import random as _rnd
        _counters.inc("jit.traces")  # body runs as python only per trace
        # save the concrete host bindings: they are restored in the
        # finally block so tracers never leak into Parameter._data /
        # optimizer accumulators after the trace finishes
        saved_params = [(p, p._data) for _, p in model.named_parameters()]
        saved_buffers = [(b, b._data) for _, b in model.named_buffers()]
        saved_accs = opt._accumulators
        saved_masters = opt._master_weights
        prev_lr = opt._learning_rate
        prev_step_count = opt._step_count
        prev_grad_mode = STATE.grad_enabled
        prev_chain = _rnd._TRACE_CHAIN[0]
        use_key, carry_key = jax.random.split(rng_key)
        _rnd._TRACE_CHAIN[0] = _rnd._TraceKeyChain(use_key)
        STATE.tracing_depth += 1
        try:
            bind_layer_state(model, params, buffers)
            bind_optimizer_state(opt, opt_state)
            opt._learning_rate = lr
            if self.mesh is not None:
                args = self._constrain_batch(args)
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
            checks = {}
            if check_nan_inf:
                # FLAGS_check_nan_inf (reference: eager nan_inf_utils.cc
                # hook): finite-ness of loss / per-param grads / updated
                # params traced INTO the program; host side raises with
                # span context.  Under a GradScaler the grads seen here
                # are post-unscale safe values and found_inf reports the
                # overflow the scaler already handles.
                checks["loss"] = jnp.all(jnp.isfinite(
                    loss._data.astype(jnp.float32)))
                for k, p in model.named_parameters():
                    if p.grad is not None:
                        checks["grad:" + k] = jnp.all(jnp.isfinite(
                            p.grad._data.astype(jnp.float32)))
            mets = {}
            if metrics_on:
                # grad global-norm over the (post-unscale) grads the
                # optimizer is about to consume — traced into the program,
                # so metrics-on costs one fused reduction, zero host work
                sq = jnp.zeros((), jnp.float32)
                for _, p in model.named_parameters():
                    if p.grad is not None:
                        g32 = p.grad._data.astype(jnp.float32)
                        sq = sq + jnp.sum(g32 * g32)
                mets["grad_norm"] = jnp.sqrt(sq)
            opt.step()
            opt.clear_grad()
            new_params = {k: p._data for k, p in model.named_parameters()}
            new_buffers = {k: b._data for k, b in model.named_buffers()}
            new_opt = optimizer_state(opt)
            if scaler is not None:
                new_params = _skip_select(found, params, new_params)
                new_opt = _skip_select(found, opt_state, new_opt)
                sstate = scaler._traced_update(sstate, found)
            if self.mesh is not None:
                new_params, new_buffers, new_opt = self._pin_carry(
                    new_params, new_buffers, new_opt)
            if check_nan_inf:
                for k, v in new_params.items():
                    checks["param:" + k] = jnp.all(jnp.isfinite(
                        v.astype(jnp.float32)))
                if scaler is not None:
                    checks["found_inf"] = found
            if metrics_on:
                if scaler is not None:
                    mets["skip"] = found.astype(jnp.float32)
                    mets["scale"] = jnp.reshape(jnp.asarray(
                        sstate["scale"], jnp.float32), (-1,))[0]
                else:
                    mets["skip"] = jnp.zeros((), jnp.float32)
                    mets["scale"] = jnp.ones((), jnp.float32)
            loss_data = loss._data
        finally:
            STATE.tracing_depth -= 1
            _rnd._TRACE_CHAIN[0] = prev_chain
            opt._learning_rate = prev_lr
            # the host step counter is owned by __call__ (one bump per
            # step); the trace-time opt.step() bump must not stick
            opt._step_count = prev_step_count
            STATE.grad_enabled = prev_grad_mode
            for p, d in saved_params:
                p._data = d
                p.grad = None
            for b, d in saved_buffers:
                b._data = d
            opt._accumulators = saved_accs
            opt._master_weights = saved_masters
        return (loss_data, new_params, new_buffers, new_opt, sstate,
                carry_key, checks, mets)

    def _donate_argnums(self):
        # full donation including the scaler path: _skip_select consumes
        # the pre-step values inside the program, so aliasing params/
        # buffers/opt-state buffers to the outputs is still legal
        return (0, 1, 2) if self._donate else ()

    _MACC_KEYS = ("steps", "loss_sum", "grad_norm_sum", "skip_sum")

    def _macc_add(self, macc, loss, mets):
        """Fold one step's traced scalars into the donated metric
        accumulator (running totals ride the carry; harvested at sync
        boundaries by :meth:`metrics_flush`)."""
        loss32 = jnp.mean(loss.astype(jnp.float32))
        out = {"steps": macc["steps"] + 1.0,
               "loss_sum": macc["loss_sum"] + loss32,
               "grad_norm_sum": macc["grad_norm_sum"] + mets["grad_norm"],
               "skip_sum": macc["skip_sum"] + mets["skip"]}
        if self.mesh is not None:
            out = {k: self._pin(v, None) for k, v in out.items()}
        return out

    def _make_jit(self, check_nan_inf=False, metrics_on=False):
        if not metrics_on:
            def step_fn(params, buffers, opt_state, lr, rng_key, sstate,
                        args):
                return self._step_body(check_nan_inf, False, params, buffers,
                                       opt_state, lr, rng_key, sstate,
                                       args)[:7]

            return jax.jit(step_fn, donate_argnums=self._donate_argnums())

        def step_fn(params, buffers, opt_state, lr, rng_key, sstate, args,
                    macc):
            (loss, params, buffers, opt_state, sstate, rng_key, checks,
             mets) = self._step_body(check_nan_inf, True, params, buffers,
                                     opt_state, lr, rng_key, sstate, args)
            return (loss, params, buffers, opt_state, sstate, rng_key,
                    checks, self._macc_add(macc, loss, mets), mets)

        # NB: `donate + (7,) if donate else ()` would parse as
        # `(donate + (7,)) if donate else ()` (PT003) — keep the ternary
        # inside the sum so the macc arg's donation tracks the carry's
        donate = self._donate_argnums()
        return jax.jit(step_fn,
                       donate_argnums=donate + ((7,) if donate else ()))

    def _make_fused_jit(self, check_nan_inf, k, metrics_on=False):
        """Fused window program: ``jax.lax.scan`` of the single-step body
        over K stacked batches and a K-vector of learning rates — forward +
        backward + optimizer update for all K steps in ONE donated XLA
        launch.  Requires the optimizer accumulators to already exist (the
        scan carry structure must be invariant), so the first-ever window
        runs through the single-step fallback instead.  With metrics on,
        the metric accumulator joins the scan carry and the per-step
        telemetry scalars come back stacked as extra ys."""

        if not metrics_on:
            def window_fn(params, buffers, opt_state, lrs, rng_key, sstate,
                          stacked_args):
                def body(carry, xs):
                    params, buffers, opt_state, sstate, rng_key = carry
                    lr, args = xs
                    (loss, params, buffers, opt_state, sstate, rng_key,
                     checks, _) = self._step_body(check_nan_inf, False,
                                                  params, buffers, opt_state,
                                                  lr, rng_key, sstate, args)
                    return ((params, buffers, opt_state, sstate, rng_key),
                            (loss, checks))

                init = (params, buffers, opt_state, sstate, rng_key)
                ((params, buffers, opt_state, sstate, rng_key),
                 (losses, checks)) = jax.lax.scan(body, init,
                                                  (lrs, stacked_args),
                                                  length=k)
                return (losses, params, buffers, opt_state, sstate, rng_key,
                        checks)

            return jax.jit(window_fn,
                           donate_argnums=self._donate_argnums())

        def window_fn(params, buffers, opt_state, lrs, rng_key, sstate,
                      stacked_args, macc):
            def body(carry, xs):
                params, buffers, opt_state, sstate, rng_key, macc = carry
                lr, args = xs
                (loss, params, buffers, opt_state, sstate, rng_key,
                 checks, mets) = self._step_body(check_nan_inf, True,
                                                 params, buffers, opt_state,
                                                 lr, rng_key, sstate, args)
                macc = self._macc_add(macc, loss, mets)
                return ((params, buffers, opt_state, sstate, rng_key, macc),
                        (loss, checks, mets))

            init = (params, buffers, opt_state, sstate, rng_key, macc)
            ((params, buffers, opt_state, sstate, rng_key, macc),
             (losses, checks, mets)) = jax.lax.scan(body, init,
                                                    (lrs, stacked_args),
                                                    length=k)
            return (losses, params, buffers, opt_state, sstate, rng_key,
                    checks, macc, mets)

        donate = self._donate_argnums()
        return jax.jit(window_fn,
                       donate_argnums=donate + ((7,) if donate else ()))

    def _mesh_scope(self):
        """Ambient-mesh context for one call (trace, audit and dispatch
        alike): what GSPMD cannot partition — a Mosaic kernel — reads the
        mesh from here at trace time and wraps itself in ``shard_map``
        (``kernels.flash_attention``)."""
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def __call__(self, *args):
        self._call_t0_ns = time.perf_counter_ns()
        with _trace.span("jit.step"), self._mesh_scope():
            from ..io import Window
            if len(args) == 1 and isinstance(args[0], Window):
                return self._call_window(tuple(args[0]), args[0].k)
            if self.fused_steps > 1:
                # fused mode: every call takes a K-stacked window (leading
                # axis = window length on every array leaf)
                return self._call_window(args, None)
            return self._call_impl(args)

    def _note_dispatch(self, traces_before):
        """Count the dispatch that just returned as ``jit.cache_hits`` or,
        if the step body was traced for it, ``jit.cache_misses``; such a
        call is the first of a traced variant, kept from its start
        (hydrate, trace, compile or load, first dispatch) as the lifecycle
        span ``jit.first_call``."""
        traced = _counters.get("jit.traces") - traces_before
        if not traced:
            _counters.inc("jit.cache_hits")
            return
        _counters.inc("jit.cache_misses")
        _trace.lifecycle_since("jit.first_call", self._call_t0_ns,
                               traces=traced)

    def _ensure_state(self):
        from ..core.state import param_version
        if self._state is None or param_version() != self._seen_version:
            self._hydrate()
            return True
        return False

    @staticmethod
    def _strip(args):
        return jax.tree_util.tree_map(
            lambda x: x._data if isinstance(x, Tensor) else x, args,
            is_leaf=lambda x: isinstance(x, Tensor))

    @staticmethod
    def _window_len(args_data):
        for leaf in jax.tree_util.tree_leaves(args_data):
            if hasattr(leaf, "shape") and len(leaf.shape) >= 1:
                return int(leaf.shape[0])
        raise ValueError(
            "cannot infer the window length: no array leaf with a leading "
            "axis in the window args (stack batches or pass an io.Window)")

    def _call_impl(self, args):
        hydrated = self._ensure_state()
        loss = self._dispatch_single(self._strip(args),
                                     self.optimizer.get_lr())
        if hydrated:
            # first call after (re)hydration: keep the python objects fresh
            # so "step once, then inspect" retains eager semantics; the
            # steady-state path skips this entirely
            self.sync()
        from ..distributed.elastic import heartbeat
        heartbeat()  # no-op unless under the elastic launcher
        return Tensor._wrap(loss)

    def _call_window(self, args, k=None):
        """Train on a window of K stacked batches: ONE fused dispatch when
        the window is full-size and the carry structure is known, K
        single-step dispatches otherwise (first-ever window, partial tail).
        Returns the [k] vector of per-step lazy losses."""
        args_data = self._strip(args)
        if k is None:
            k = self._window_len(args_data)
        k = int(k)
        if k < 1:
            raise ValueError(f"empty dispatch window (k={k})")
        hydrated = self._ensure_state()
        # per-step lr vector, previewed WITHOUT mutating the host scheduler
        # (the scheduler advances under user control, after the window)
        lrs = self.optimizer._peek_lrs(k)
        # fused dispatch needs an invariant scan carry structure, so the
        # very first window (lazy optimizer accumulators not yet
        # materialized) runs as single steps, like any partial tail window
        if (k == self.fused_steps and k > 1
                and self.optimizer._step_count > 0):
            losses = self._dispatch_window(args_data, lrs, k)
        else:
            _counters.inc("jit.fused_fallback_steps", k)
            per_step = []
            for i in range(k):
                sliced = jax.tree_util.tree_map(
                    lambda x, _i=i: x[_i] if hasattr(x, "shape") else x,
                    args_data)
                per_step.append(self._dispatch_single(sliced, lrs[i]))
            losses = jnp.stack(per_step)
        if hydrated:
            self.sync()
        from ..distributed.elastic import heartbeat
        heartbeat()  # no-op unless under the elastic launcher
        return Tensor._wrap(losses)

    def _ensure_macc(self):
        if self._macc is None:
            z = {k: jnp.zeros((), jnp.float32) for k in self._MACC_KEYS}
            if self.mesh is not None:
                z = jax.device_put(z, self._rep)
            self._macc = z

    def _dispatch_single(self, args_data, lr_val):
        """One single-step XLA dispatch on raw array args -> raw loss."""
        _counters.inc("jit.steps")
        check = bool(_flags.flag("FLAGS_check_nan_inf"))
        mon = self.metrics is not None
        key = (check, True) if mon else check
        jit_fn = self._jits.get(key)
        fresh = jit_fn is None
        if fresh:
            jit_fn = self._jits[key] = self._make_jit(check, mon)
        if self._lr_dev is None or lr_val != self._lr_host:
            self._lr_host = lr_val
            self._lr_dev = jnp.asarray(lr_val, jnp.float32)
            if self.mesh is not None:
                # the whole carry is mesh-committed; an uncommitted
                # single-device lr scalar would make the dispatch mix
                # device sets — replicate it once per scheduler value
                self._lr_dev = jax.device_put(self._lr_dev, self._rep)
        if mon:
            self._ensure_macc()
        params, buffers, opt_state, sstate, rng_key = self._state
        if fresh and (_metrics.device_telemetry_enabled()
                      or _audit.audit_enabled()):
            cargs = (params, buffers, opt_state, self._lr_dev, rng_key,
                     sstate, args_data) + ((self._macc,) if mon else ())
            pname = f"jit.step[check={int(check)},metrics={int(mon)}]"
            if _metrics.device_telemetry_enabled():
                _metrics.capture_program_stats(pname, jit_fn, *cargs)
            donate = self._donate_argnums()
            _audit.maybe_audit(
                pname, jit_fn, *cargs,
                donate_argnums=donate + ((7,) if donate and mon else ()),
                expect_no_collectives=self.mesh is None)
        traces_before = _counters.get("jit.traces")
        _dt = (_devicetime.note(
            f"jit.step[check={int(check)},metrics={int(mon)}]")
            if _devicetime.enabled() else None)
        with _trace.span("jit.dispatch"):
            _counters.inc("jit.host.dispatches")
            _flight.record("jit.dispatch",
                           step=self.optimizer._step_count + 1, k=1)
            if mon:
                (loss, new_params, new_buffers, new_opt, new_sstate,
                 new_rng, checks, new_macc, mets) = jit_fn(
                     params, buffers, opt_state, self._lr_dev, rng_key,
                     sstate, args_data, self._macc)
                self._macc = new_macc
            else:
                (loss, new_params, new_buffers, new_opt, new_sstate,
                 new_rng, checks) = jit_fn(params, buffers, opt_state,
                                           self._lr_dev, rng_key, sstate,
                                           args_data)
        if _dt is not None:
            _devicetime.observe(_dt, (loss, new_params, new_opt))
        self._note_dispatch(traces_before)
        # bump AFTER the call: at trace time opt.step() does its own bump, so
        # t-based rules (NAdam/RAdam) see the same count an eager step would
        self.optimizer._step_count += 1
        self._state = (new_params, new_buffers, new_opt, new_sstate, new_rng)
        self._synced = False
        if mon:
            self._note_metrics(loss, mets, (lr_val,), 1, args_data,
                               stacked=False)
        if check and checks:
            self._raise_if_nonfinite(checks)
        return loss

    def _dispatch_window(self, args_data, lrs, k):
        """One fused K-step XLA dispatch on K-stacked args -> raw [k]
        losses."""
        _counters.inc("jit.steps", k)
        _counters.inc("jit.fused_windows")
        check = bool(_flags.flag("FLAGS_check_nan_inf"))
        mon = self.metrics is not None
        cache_key = (check, k, True) if mon else (check, k)
        jit_fn = self._fused_jits.get(cache_key)
        fresh = jit_fn is None
        if fresh:
            jit_fn = self._fused_jits[cache_key] = \
                self._make_fused_jit(check, k, mon)
        lrs_t = tuple(float(v) for v in lrs)
        if self._lrs_dev is None or lrs_t != self._lrs_host:
            self._lrs_host = lrs_t
            self._lrs_dev = jnp.asarray(lrs_t, jnp.float32)
            if self.mesh is not None:
                self._lrs_dev = jax.device_put(self._lrs_dev, self._rep)
        if mon:
            self._ensure_macc()
        params, buffers, opt_state, sstate, rng_key = self._state
        if fresh and (_metrics.device_telemetry_enabled()
                      or _audit.audit_enabled()):
            cargs = (params, buffers, opt_state, self._lrs_dev, rng_key,
                     sstate, args_data) + ((self._macc,) if mon else ())
            pname = f"jit.window[check={int(check)},k={k},metrics={int(mon)}]"
            if _metrics.device_telemetry_enabled():
                _metrics.capture_program_stats(pname, jit_fn, *cargs)
            donate = self._donate_argnums()
            _audit.maybe_audit(
                pname, jit_fn, *cargs,
                donate_argnums=donate + ((7,) if donate and mon else ()),
                expect_no_collectives=self.mesh is None)
        traces_before = _counters.get("jit.traces")
        _dt = (_devicetime.note(
            f"jit.window[check={int(check)},k={k},metrics={int(mon)}]")
            if _devicetime.enabled() else None)
        with _trace.span("jit.dispatch"):
            _counters.inc("jit.host.dispatches")
            _flight.record("jit.dispatch",
                           step=self.optimizer._step_count + k, k=k)
            if mon:
                (losses, new_params, new_buffers, new_opt, new_sstate,
                 new_rng, checks, new_macc, mets) = jit_fn(
                     params, buffers, opt_state, self._lrs_dev, rng_key,
                     sstate, args_data, self._macc)
                self._macc = new_macc
            else:
                (losses, new_params, new_buffers, new_opt, new_sstate,
                 new_rng, checks) = jit_fn(params, buffers, opt_state,
                                           self._lrs_dev, rng_key, sstate,
                                           args_data)
        if _dt is not None:
            _devicetime.observe(_dt, (losses, new_params, new_opt))
        self._note_dispatch(traces_before)
        self.optimizer._step_count += k
        self._state = (new_params, new_buffers, new_opt, new_sstate, new_rng)
        self._synced = False
        if mon:
            self._note_metrics(losses, mets, lrs_t, k, args_data,
                               stacked=True)
        if check and checks:
            self._raise_if_nonfinite(checks, window=k)
        return losses

    # -- train-metrics harvest (profiler.metrics) ----------------------------
    def _infer_tokens(self, args_data, stacked):
        """Tokens per training step from the batch shape: B*S of the first
        >=2-D array leaf (ids [B, S]), else the leading batch size; with a
        K-stacked window the leading window axis is skipped."""
        skip = 1 if stacked else 0
        for leaf in jax.tree_util.tree_leaves(args_data):
            shape = getattr(leaf, "shape", None)
            if shape is None or len(shape) <= skip:
                continue
            dims = shape[skip:]
            if len(dims) >= 2:
                return int(dims[0]) * int(dims[1])
            return int(dims[0])
        return None

    def _count_params(self):
        if self._n_params is None:
            import math
            self._n_params = sum(
                int(math.prod(p._data.shape))
                for _, p in self.model.named_parameters())
        return self._n_params

    def _note_metrics(self, loss, mets, lrs, k, args_data, stacked):
        """Queue one dispatch's lazy metric refs (device arrays — NOT read
        here) plus host-side context; :meth:`metrics_flush` materializes
        them at the next sync boundary."""
        if not self._tok_cached:
            self._tokens_per_step = self._infer_tokens(args_data, stacked)
            self._tok_cached = True
        now = time.perf_counter()
        dt = (now - self._last_dispatch_t
              if self._last_dispatch_t is not None else None)
        self._last_dispatch_t = now
        self._pending.append({
            "gstep0": self.optimizer._step_count - k + 1, "k": k,
            "loss": loss, "mets": mets, "lrs": lrs, "dt": dt,
            "tokens": self._tokens_per_step,
        })
        if len(self._pending) >= self._pending_cap:
            # backstop for loops that never hit a sync boundary: one host
            # readback of tiny scalars (no jit.syncs, no state rebind)
            self.metrics_flush()

    def metrics_flush(self):
        """Harvest pending per-step metrics into the MetricsLogger: one
        host readback of the queued scalar refs + the donated accumulator.
        Runs automatically at every existing sync boundary (``sync()``,
        ``export_resume_state()``) — never adds a ``jit.syncs`` tick or an
        extra dispatch."""
        if self.metrics is None or (not self._pending
                                    and self._macc is None):
            return
        import numpy as np
        pending, self._pending = self._pending, []
        peak_tflops = float(_flags.flag("FLAGS_peak_tflops") or 0.0)
        n_params = self._count_params()
        for rec in pending:
            k = rec["k"]
            loss = np.atleast_1d(np.asarray(rec["loss"], np.float64))
            mvals = {name: np.atleast_1d(np.asarray(v, np.float64))
                     for name, v in rec["mets"].items()}
            step_time = rec["dt"] / k if rec["dt"] is not None else None
            tokens = rec["tokens"]
            tok_s = (tokens / step_time
                     if tokens and step_time and step_time > 0 else None)
            mfu = (6.0 * n_params * tok_s / (peak_tflops * 1e12)
                   if tok_s and n_params and peak_tflops > 0 else None)
            for i in range(k):
                gstep = rec["gstep0"] + i

                def _at(a):
                    return float(a[i] if a.size > 1 else a[0])

                self.metrics.log(
                    step=gstep, loss=_at(loss),
                    grad_norm=_at(mvals["grad_norm"]),
                    lr=float(rec["lrs"][i if len(rec["lrs"]) > 1 else 0]),
                    scaler_scale=_at(mvals["scale"]),
                    scaler_skip=_at(mvals["skip"]),
                    step_time_s=step_time, tok_s=tok_s, mfu=mfu)
            _flight.record_point("loss", float(loss[-1]),
                                 step=rec["gstep0"] + k - 1)
        if self._macc is not None:
            acc = {name: float(np.asarray(v))
                   for name, v in self._macc.items()}
            steps = acc["steps"]
            if steps > 0:
                _counters.set_gauge("train.steps_accum", steps)
                _counters.set_gauge("train.loss_mean",
                                    acc["loss_sum"] / steps)
                _counters.set_gauge("train.grad_norm_mean",
                                    acc["grad_norm_sum"] / steps)
                _counters.set_gauge("train.skip_steps", acc["skip_sum"])

    def _raise_if_nonfinite(self, checks, window=1):
        """FLAGS_check_nan_inf host side: pull the traced finite-ness bits
        (a deliberate host sync — this is a debug mode) and raise with the
        offending phase names, the step index inside a fused window, and
        the current span context."""
        import numpy as np
        with _trace.span("jit.nan_inf_check"):
            _counters.inc("jit.nan_inf_checks")
            finfo = checks.get("found_inf")
            overflow = (np.atleast_1d(np.asarray(finfo))
                        if (self.scaler is not None and finfo is not None)
                        else None)
            bad_by_step = {}
            for name in sorted(checks):
                if name == "found_inf":
                    continue
                arr = np.atleast_1d(np.asarray(checks[name]))
                for i, ok in enumerate(arr):
                    if bool(ok):
                        continue
                    if overflow is not None and bool(
                            overflow[i if overflow.size > 1 else 0]):
                        # fp16 overflow step: the scaler skipped the update
                        # and will shrink the scale — expected dynamics,
                        # not a defect
                        continue
                    bad_by_step.setdefault(i, []).append(name)
            if not bad_by_step:
                return
            _counters.inc("jit.nan_inf_hits")
            i = min(bad_by_step)
            bad = bad_by_step[i]
            shown = ", ".join(bad[:8]) + (f" (+{len(bad) - 8} more)"
                                          if len(bad) > 8 else "")
            gstep = self.optimizer._step_count - window + i + 1
            where = (f"train step {gstep} (step {i} of a {window}-step "
                     f"fused window)" if window > 1
                     else f"train step {gstep}")
            stack = _trace.current_stack()
            ctx = f" [active spans: {' > '.join(stack)}]" if stack else ""
            # postmortem before the raise: the flight bundle names the
            # failing step and the non-finite tensors
            _flight.dump("nan_inf", {
                "step": gstep, "window": window, "window_index": i,
                "bad": bad[:32], "where": where})
            raise FloatingPointError(
                f"FLAGS_check_nan_inf: non-finite values at {where}: "
                f"{shown}{ctx}")




@contextlib.contextmanager
def eval_mode(layer):
    """Temporarily put a Layer in eval mode, restoring the EXACT
    per-sublayer training flags afterwards (a bare .train() would flatten
    mixed-mode models — e.g. re-enable a deliberately frozen BatchNorm)."""
    states = [(sub, sub.training)
              for _, sub in layer.named_sublayers(include_self=True)]
    layer.eval()
    try:
        yield
    finally:
        for sub, was in states:
            sub.training = was


def functional_forward(layer, fn=None):
    """The functionalize-a-Layer trace harness shared by jit.save and
    hapi.flops: returns pure(params, buffers, *xs) -> pytree of raw
    arrays, with parameters bound, tracing depth set, and grad off."""
    call = fn if fn is not None else layer.forward

    def pure(params, buffers, *xs):
        bind_layer_state(layer, params, buffers)
        STATE.tracing_depth += 1
        try:
            with no_grad_guard():
                out = call(*[Tensor._wrap(x) for x in xs])
        finally:
            STATE.tracing_depth -= 1
        return jax.tree_util.tree_map(
            lambda t: t._data if isinstance(t, Tensor) else t, out,
            is_leaf=lambda t: isinstance(t, Tensor))

    return pure


# ---------------------------------------------------------------------------
# save / load — serialized-program deployment artifact.
#
# Reference analogue: paddle.jit.save → a Program + params that
# fluid/jit/layer.h:44 (jit::Layer) reloads and runs WITHOUT the original
# python class.  TPU-native twin: jax.export serializes the traced
# StableHLO module (+ input/output tree specs) to `path + ".pdmodel"`, the
# weights go to `path + ".pdparams"` (npz); `load` deserializes into a
# TranslatedLayer whose __call__ executes the compiled program — no source
# class needed, loadable in a fresh process.
# ---------------------------------------------------------------------------
def save(layer, path, input_spec=None, **configs):
    """Export `layer.forward` (or a StaticFunction) as a deployment artifact.

    input_spec: list of paddle_tpu.static.InputSpec (or Tensors /
    ShapeDtypeStructs) describing the forward arguments.  Required unless
    the layer was called at least once through to_static (then the traced
    signature is reused is NOT implemented — pass input_spec).
    """
    import json
    import numpy as np
    from jax import export as jexport

    fn = layer.forward if _is_layer(layer) else layer
    target = layer if _is_layer(layer) else getattr(layer, "_layer", None)
    if target is None:
        raise ValueError("jit.save needs a Layer (or to_static-wrapped "
                         "Layer method)")
    if input_spec is None:
        raise ValueError(
            "jit.save requires input_spec=[InputSpec(shape, dtype), ...] "
            "describing the forward arguments (reference: jit/api.py save)")

    _sym_counter = [0]

    def _to_struct(s):
        if hasattr(s, "shape") and hasattr(s, "dtype"):
            dims = []
            for d in list(s.shape):
                if d is None or (isinstance(d, int) and d < 0):
                    # dynamic dim → jax.export symbolic dimension, so the
                    # artifact accepts any size at that axis (paddle's
                    # InputSpec([None, H]) dynamic-batch idiom)
                    _sym_counter[0] += 1
                    dims.append(f"_dyn{_sym_counter[0]}")
                else:
                    dims.append(str(int(d)))
            dt = str(s.dtype)
            if "int64" in dt:
                # x64 is disabled framework-wide: int64 tensors ARE int32
                import warnings
                warnings.warn("jit.save: int64 input_spec exported as int32 "
                              "(jax x64 disabled)", RuntimeWarning,
                              stacklevel=3)
            dt = {"paddle.float32": "float32", "paddle.int64": "int32",
                  "int64": "int32"}.get(dt, dt)
            from jax import export as jexport
            shape = jexport.symbolic_shape(", ".join(dims)) \
                if any(d.startswith("_dyn") for d in dims) \
                else tuple(int(d) for d in dims)
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dt))
        raise TypeError(f"unsupported input_spec entry: {s!r}")

    structs = [_to_struct(s) for s in input_spec]
    params, buffers = layer_state(target)
    pure = functional_forward(target, fn)
    with eval_mode(target):
        try:
            p_structs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
            b_structs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), buffers)
            exported = jexport.export(jax.jit(pure))(p_structs, b_structs,
                                                     *structs)
            blob = exported.serialize()
        finally:
            bind_layer_state(target, params, buffers)
    with open(path + ".pdmodel", "wb") as f:
        f.write(blob)
    np.savez(path + ".pdparams",
             **{f"p##{k}": np.asarray(v) for k, v in params.items()},
             **{f"b##{k}": np.asarray(v) for k, v in buffers.items()})
    with open(path + ".pdmeta.json", "w") as f:
        json.dump({"inputs": [[[str(d) for d in s.shape], str(s.dtype)]
                              for s in structs],
                   "format": "stablehlo-v1"}, f)


class TranslatedLayer:
    """Runs a deserialized exported program (reference: jit::Layer,
    fluid/jit/layer.h:44 + python TranslatedLayer, jit/translated_layer.py).
    Holds weights + the compiled StableHLO module; no original class."""

    def __init__(self, exported, params, buffers):
        self._exported = exported
        self._params = params
        self._buffers = buffers
        self.training = False

    def __call__(self, *args):
        xs = [a._data if isinstance(a, Tensor) else jnp.asarray(a)
              for a in args]
        out = self._exported.call(self._params, self._buffers, *xs)
        return jax.tree_util.tree_map(
            lambda a: Tensor._wrap(a) if isinstance(a, jax.Array) else a,
            out)

    forward = __call__

    def eval(self):
        return self

    def state_dict(self):
        d = {k: Tensor._wrap(v) for k, v in self._params.items()}
        d.update({k: Tensor._wrap(v) for k, v in self._buffers.items()})
        return d


def load(path, **configs):
    """Load a jit.save artifact into a TranslatedLayer — works in a fresh
    process without the original model class on the path."""
    import numpy as np
    from jax import export as jexport
    with open(path + ".pdmodel", "rb") as f:
        blob = f.read()
    if blob[:1] == b"\x80":  # legacy pickle artifact (pre-stablehlo)
        raise RuntimeError(
            "this artifact was written by the old pickle-based jit.save; "
            "re-export with the current version")
    exported = jexport.deserialize(blob)
    params, buffers = {}, {}
    with np.load(path + ".pdparams.npz") as z:
        for k in z.files:
            kind, name = k.split("##", 1)
            (params if kind == "p" else buffers)[name] = jnp.asarray(z[k])
    return TranslatedLayer(exported, params, buffers)


_static_mode = [False]


def enable_static():
    _static_mode[0] = True


def disable_static():
    _static_mode[0] = False


def in_static_mode():
    return _static_mode[0]


def enable_to_static(flag=True):
    pass


# -- dy2static logging knobs (reference: jit/dy2static/logging_utils.py) ----
_CODE_LEVEL = [0]
_VERBOSITY = [0]


def set_code_level(level=100, also_to_stdout=False):
    """API-parity knob.  The reference's dy2static prints the transformed
    source at this level; here tracing is jax.jit, so there is no
    transformed source to print — the value is stored for introspection
    only."""
    _CODE_LEVEL[0] = level


def set_verbosity(level=0, also_to_stdout=False):
    _VERBOSITY[0] = level
