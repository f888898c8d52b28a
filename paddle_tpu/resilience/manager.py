"""CheckpointManager: complete-training-state snapshots with atomic commit,
async overlap, checksums, retry, and keep-last-N GC.

A *complete* snapshot of a ``jit.CompiledTrainStep`` run is more than the
parameters: it is params + buffers + optimizer accumulators/master weights +
``GradScaler`` dynamic-loss-scale counters + ``LRScheduler`` position + the
global RNG key chain + the step's in-graph RNG carry key + the data-iterator
cursor (epoch, batch offset).  The manager captures all of it with exactly
ONE counter-gated ``step.sync()`` (pointer rebinds — no extra host transfers
beyond the D2H copies of the save itself) and restores it so the resumed
run's loss trajectory is bit-identical to an uninterrupted one.

Layout (one directory per save, committed by an atomic directory rename)::

    root/
      step-00000004/               <- committed (manifest present)
        MANIFEST.json              <- scalars + per-array shape/dtype table
        0_0.0.distcp.npz           <- chunk data (distributed/checkpoint)
        0.0.metadata.json          <- chunk index incl. per-chunk crc32
      .tmp-step-00000008/          <- in-flight or crashed save: ignored

Write protocol: stage everything into ``.tmp-step-N`` (the
``distributed/checkpoint`` writer fsyncs chunk + metadata files), write
``MANIFEST.json`` via tmp + fsync + rename, then ``os.replace`` the staging
directory to ``step-N`` — the commit point.  A writer killed at ANY earlier
moment leaves only an ignored ``.tmp`` directory; the previous checkpoint
stays loadable.  Transient ``OSError`` during the write is retried with
exponential backoff (``resilience.retries``); async mode runs the disk work
on a daemon thread so the save overlaps the next fused window (the D2H
snapshot itself happens synchronously, before the donated device buffers
can be reused by the next dispatch).

On restore, per-chunk crc32 checksums are verified (a mismatch raises
``CheckpointCorrupt`` naming the chunk, counted under
``resilience.corrupt_detected``) and the manager falls back to the next
older committed checkpoint.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import threading
import time

import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..distributed import checkpoint as _dckpt
from ..profiler import counters as _counters
from ..profiler import flight as _flight
from ..profiler import host_tracer as _trace
from ..profiler import metrics as _metrics
from ..tensor.random import default_generator
from . import faultinject as _fi

CheckpointCorrupt = _dckpt.CheckpointCorrupt

_STEP_DIR = re.compile(r"^step-(\d{8})$")
_TMP_PREFIX = ".tmp-"
_MANIFEST = "MANIFEST.json"


class CheckpointWriteError(RuntimeError):
    """A checkpoint save failed permanently (retries exhausted)."""


class CheckpointLayoutError(RuntimeError):
    """The checkpoint's array layout is incompatible with the live training
    state (leaf shape mismatch — a different model, not a different mesh).
    Deliberately NOT a fallback-to-older-checkpoint condition: every older
    save of the same run would mismatch the same way, so the manager raises
    immediately instead of silently restoring nothing.  Mere mesh-shape
    differences do NOT raise — restore reshards (see ``resharded`` in the
    restore info)."""


def _np(x):
    """Force an owning host copy (the device buffer may be donated to the
    very next dispatch while an async writer is still serialising)."""
    if isinstance(x, Tensor):
        x = x._data
    return np.array(x, copy=True)


def _capture(x):
    """Snapshot one state leaf for the writer: a multi-device array becomes
    per-shard host chunks (synchronous D2H of each unique local shard —
    never a gathered global copy), anything else a plain owning ndarray."""
    import jax
    data = x._data if isinstance(x, Tensor) else x
    if isinstance(data, jax.Array) and len(data.sharding.device_set) > 1:
        return _dckpt.ShardChunks.capture(data)
    return _np(data)


def _mesh_desc(mesh):
    """JSON-able mesh identity recorded in the manifest (axis names +
    sizes), compared on restore to detect resharding."""
    if mesh is None:
        return None
    return {"axis_names": [str(a) for a in mesh.axis_names],
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names]}


def _spec_json(spec):
    """PartitionSpec -> JSON (None | axis-name | [axis-names] per dim)."""
    if spec is None:
        return None
    return [None if axes is None
            else (axes if isinstance(axes, str) else [str(a) for a in axes])
            for axes in spec]


def _param_names(optimizer):
    """The optimizer state_dict's name for each param, in list order —
    the bridge between volatile auto-generated names and stable positions."""
    return [p.name or f"param_{i}"
            for i, p in enumerate(optimizer._parameter_list or [])]


class CheckpointManager:
    """Snapshot/restore the complete state of a ``jit.CompiledTrainStep``.

    Parameters
    ----------
    root: checkpoint directory (created if missing).
    keep_last: retain this many newest committed checkpoints (older ones
        are garbage-collected after each successful save).
    async_save: default for ``save(blocking=...)`` — when True the disk
        write runs on a background thread, overlapping the next window.
    retries / backoff_s: transient ``OSError`` writes are retried up to
        ``retries`` times with exponential backoff starting at
        ``backoff_s`` seconds.
    """

    def __init__(self, root, keep_last=3, async_save=False, retries=3,
                 backoff_s=0.01):
        self.root = str(root)
        self.keep_last = int(keep_last)
        self.async_save = bool(async_save)
        self.retries = int(retries)
        self.backoff_s = float(backoff_s)
        os.makedirs(self.root, exist_ok=True)
        self._thread = None
        self._error = None
        self._save_ordinal = 0  # deterministic index for fault schedules

    # -- discovery -----------------------------------------------------------
    def _committed(self):
        """Sorted list of committed save step numbers."""
        steps = []
        for name in os.listdir(self.root):
            m = _STEP_DIR.match(name)
            if m and os.path.exists(os.path.join(self.root, name, _MANIFEST)):
                steps.append(int(m.group(1)))
        return sorted(steps)

    def _dir(self, step_no):
        return os.path.join(self.root, f"step-{step_no:08d}")

    def latest(self):
        """Newest committed checkpoint's global step, or None."""
        steps = self._committed()
        return steps[-1] if steps else None

    # -- save ----------------------------------------------------------------
    def save(self, train_step, global_step, *, scheduler=None, cursor=None,
             blocking=None):
        """Snapshot the complete training state at ``global_step``.

        The host-side snapshot (one ``sync()`` + D2H copies) always happens
        on the calling thread; with ``blocking=False`` only the disk write
        is deferred to a daemon thread (at most one in flight — a new save
        first joins the previous writer).  ``cursor`` is the data-iterator
        position, e.g. ``{"epoch": 0, "offset": 12}`` (batches consumed in
        the epoch, as reported by ``io.DevicePrefetcher.consumed``).
        """
        if blocking is None:
            blocking = not self.async_save
        self.wait()  # serialize writers; surfaces a prior async failure
        ordinal = self._save_ordinal
        self._save_ordinal += 1
        with _trace.span("resilience.snapshot"):
            arrays, manifest = self._snapshot(train_step, int(global_step),
                                              scheduler, cursor)
        if blocking:
            self._write(arrays, manifest, int(global_step), ordinal)
        else:
            def _guarded():
                try:
                    self._write(arrays, manifest, int(global_step), ordinal)
                except BaseException as e:  # surfaced by wait()/next save
                    self._error = e
            self._thread = threading.Thread(target=_guarded, daemon=True)
            self._thread.start()

    def _snapshot(self, train_step, global_step, scheduler, cursor):
        """Build (flat ndarray dict, manifest) on the caller thread.

        ``export_resume_state`` performs THE one counter-gated sync; the
        subsequent ``state_dict()`` reads see already-synced objects and do
        no further host bind work.
        """
        carry = train_step.export_resume_state()
        opt = train_step.optimizer
        mesh = getattr(train_step, "mesh", None)
        model_sd = train_step.model.state_dict()
        arrays = {"rng/carry": carry,
                  "rng/host": _np(default_generator().get_state())}
        specs = {}
        for name, t in model_sd.items():
            key = f"model/{name}"
            # post-sync, state_dict tensors wrap the live (possibly mesh-
            # sharded) device arrays: multi-device leaves save as per-shard
            # chunks, single-device leaves as before
            arrays[key] = _capture(t)
            if mesh is not None:
                smap = getattr(train_step, "_param_specs", {})
                bmap = getattr(train_step, "_buffer_specs", {})
                specs[key] = _spec_json(smap.get(name, bmap.get(name)))
        if mesh is not None and train_step._state is not None:
            # sharded save: read accumulators/master weights straight from
            # the device-resident carry (optimizer.state_dict() would
            # gather every leaf to one host ndarray — the opposite of a
            # per-shard save); keys stay positional "p<i>" exactly like the
            # host path below, so restore is layout-agnostic
            # (the carry is keyed by parameter position already:
            # jit.optimizer_state)
            bypos = getattr(train_step, "_bypos", {})
            dev_opt = train_step._state[2]
            for accname, store in dev_opt["acc"].items():
                for i, v in store.items():
                    key = f"opt/acc/{accname}/p{i}"
                    arrays[key] = _capture(v)
                    specs[key] = _spec_json(bypos.get(i))
            for i, v in dev_opt["master"].items():
                key = f"opt/master/p{i}"
                arrays[key] = _capture(v)
                specs[key] = _spec_json(bypos.get(i))
            lr = opt._learning_rate
            opt_step = int(opt._step_count)
            lr_sd = lr.state_dict() if hasattr(lr, "state_dict") else None
        else:
            opt_sd = opt.state_dict()
            # optimizer state_dict keys are param NAMES, which for auto-
            # named params ("generated_tensor_N") depend on a process-global
            # counter — a restarted process numbers them differently.
            # Checkpoint keys must be the param's POSITION in the parameter
            # list, which is construction order and stable across restarts.
            pindex = {n: f"p{i}" for i, n in enumerate(_param_names(opt))}
            for accname, store in opt_sd["accumulators"].items():
                for pname, v in store.items():
                    arrays[f"opt/acc/{accname}/"
                           f"{pindex.get(pname, pname)}"] = _np(v)
            for pname, v in opt_sd["master_weights"].items():
                arrays[f"opt/master/{pindex.get(pname, pname)}"] = _np(v)
            opt_step = int(opt_sd.get("step", 0))
            lr_sd = opt_sd.get("LR_Scheduler") or None
        host = {"global_step": global_step,
                "cursor": dict(cursor or {}),
                "opt_step": opt_step,
                "lr_scheduler": lr_sd,
                "scheduler": (scheduler.state_dict()
                              if scheduler is not None else None),
                "scaler": (train_step.scaler.state_dict()
                           if train_step.scaler is not None else None),
                "fused_steps": int(getattr(train_step, "fused_steps", 1))}
        manifest = {"format": 1, "step": global_step, "host": host,
                    "mesh": _mesh_desc(mesh),
                    "arrays": {k: {"shape": list(v.shape),
                                   "dtype": str(v.dtype),
                                   "spec": specs.get(k)}
                               for k, v in arrays.items()}}
        return arrays, manifest

    def _write(self, arrays, manifest, step_no, ordinal):
        final = self._dir(step_no)
        tmp = os.path.join(self.root, f"{_TMP_PREFIX}step-{step_no:08d}")
        t0 = time.perf_counter()
        attempt = 0
        with _trace.span("resilience.save"):
            while True:
                try:
                    _fi.maybe_fault("ckpt_write", ordinal)
                    if os.path.isdir(tmp):
                        shutil.rmtree(tmp)
                    os.makedirs(tmp)
                    _dckpt.save_state_dict(arrays, tmp)
                    # a writer killed HERE (chunks on disk, no manifest, no
                    # rename) leaves only an ignored .tmp dir
                    _fi.maybe_fault("ckpt_crash", ordinal)
                    mtmp = os.path.join(tmp, _MANIFEST + ".tmp")
                    with open(mtmp, "w") as f:
                        json.dump(manifest, f)
                        f.flush()
                        os.fsync(f.fileno())
                    os.replace(mtmp, os.path.join(tmp, _MANIFEST))
                    if os.path.isdir(final):
                        shutil.rmtree(final)
                    os.replace(tmp, final)  # the commit point
                    break
                except OSError as e:
                    attempt += 1
                    if attempt > self.retries:
                        _counters.inc("resilience.save_failures")
                        raise CheckpointWriteError(
                            f"checkpoint save at step {step_no} failed "
                            f"after {attempt} attempts: {e}") from e
                    _counters.inc("resilience.retries")
                    time.sleep(self.backoff_s * (2 ** (attempt - 1)))
        try:
            dfd = os.open(self.root, os.O_RDONLY)
            try:
                os.fsync(dfd)  # persist the rename itself
            finally:
                os.close(dfd)
        except OSError:
            pass
        _counters.inc("resilience.saves")
        save_ms = int((time.perf_counter() - t0) * 1000)
        _metrics.observe("resilience.save_ms", save_ms, unit="ms",
                         sum_counter=True)
        _flight.record("ckpt.save", step=step_no, ms=save_ms)
        self._gc()

    def _gc(self):
        steps = self._committed()
        for step_no in steps[:-self.keep_last] if self.keep_last > 0 else []:
            shutil.rmtree(self._dir(step_no), ignore_errors=True)
            _counters.inc("resilience.gc_removed")
        # stale staging dirs from crashed writers (never the in-flight one:
        # _gc only runs on the single serialized writer, post-commit)
        for name in os.listdir(self.root):
            if name.startswith(_TMP_PREFIX):
                shutil.rmtree(os.path.join(self.root, name),
                              ignore_errors=True)

    def wait(self, suppress=False):
        """Join the in-flight async writer.  Re-raises its error unless
        ``suppress`` — then the failure is only counted/logged, which is
        what a recovery path wants (the live state is still good)."""
        t, self._thread = self._thread, None
        if t is not None:
            t.join()
        err, self._error = self._error, None
        if err is not None and not suppress:
            raise err

    # -- restore -------------------------------------------------------------
    def restore(self, train_step, *, scheduler=None):
        """Restore the newest loadable checkpoint into ``train_step``'s
        model/optimizer/scaler and the global RNG chain.  Falls back to
        older checkpoints on corruption.  Returns a dict with ``step``,
        ``cursor`` and ``path``, or None when no checkpoint exists."""
        self.wait(suppress=True)
        last_exc = None
        for step_no in reversed(self._committed()):
            path = self._dir(step_no)
            try:
                t0 = time.perf_counter()
                with _trace.span("resilience.restore"):
                    info = self._restore_from(path, train_step, scheduler)
                _counters.inc("resilience.restores")
                restore_ms = (time.perf_counter() - t0) * 1000
                _metrics.observe("resilience.restore_ms", restore_ms,
                                 unit="ms")
                _flight.record("ckpt.restore", step=info["step"],
                               ms=int(restore_ms))
                return info
            except (CheckpointCorrupt, ValueError, KeyError, OSError,
                    json.JSONDecodeError) as e:
                if not isinstance(e, CheckpointCorrupt):
                    # crc failures are counted at the reader; count other
                    # unloadable-checkpoint shapes here
                    _counters.inc("resilience.corrupt_detected")
                last_exc = e
                continue
        if last_exc is not None:
            raise CheckpointCorrupt(
                f"no loadable checkpoint under {self.root}; newest failure: "
                f"{type(last_exc).__name__}: {last_exc}") from last_exc
        return None

    def _restore_from(self, path, train_step, scheduler):
        with open(os.path.join(path, _MANIFEST)) as f:
            manifest = json.load(f)
        host = manifest["host"]
        saved_mesh = manifest.get("mesh")
        live_mesh_desc = _mesh_desc(getattr(train_step, "mesh", None))
        # resharding is detected from the manifest's recorded mesh identity
        # (and performed below: chunks reassemble under the LIVE mesh's
        # shardings at re-hydrate); an incompatible LAYOUT — different leaf
        # shapes — is a different model and raises immediately
        resharded = (saved_mesh != live_mesh_desc
                     and (saved_mesh or live_mesh_desc) is not None)
        # flush + drop device state FIRST: the bump_param_version calls
        # below must not rebind stale pre-restore arrays over loaded data
        train_step.invalidate()
        model_sd = train_step.model.state_dict()
        targets = {}
        for key, spec in manifest["arrays"].items():
            if key.startswith("model/"):
                name = key[len("model/"):]
                if name not in model_sd:
                    raise KeyError(
                        f"checkpoint tensor {key!r} has no target in the "
                        "live model")
                tgt = model_sd[name]
                if tuple(tgt.shape) != tuple(spec["shape"]):
                    raise CheckpointLayoutError(
                        f"checkpoint leaf {key!r} has shape "
                        f"{tuple(spec['shape'])} (saved on mesh "
                        f"{saved_mesh}, spec {spec.get('spec')}), but the "
                        f"live model tensor is {tuple(tgt.shape)} on mesh "
                        f"{live_mesh_desc} — incompatible layout, not a "
                        "resharding; refusing to restore")
                targets[key] = tgt
            else:
                targets[key] = Tensor._wrap(jnp.zeros(
                    tuple(spec["shape"]), dtype=spec["dtype"]))
        _dckpt.load_state_dict(targets, path)  # verifies per-chunk crc32
        if resharded:
            _counters.inc("resilience.resharded_restores")
        # optimizer: reassemble the name-keyed state dict it expects,
        # translating the checkpoint's positional "p<i>" keys back to THIS
        # process's live param names (see _snapshot)
        live = _param_names(train_step.optimizer)

        def _pname(tok):
            if tok.startswith("p") and tok[1:].isdigit() and \
                    int(tok[1:]) < len(live):
                return live[int(tok[1:])]
            return tok
        opt_sd = {"accumulators": {}, "master_weights": {},
                  "step": int(host.get("opt_step", 0)),
                  "LR_Scheduler": host.get("lr_scheduler") or {}}
        for key, t in targets.items():
            if key.startswith("opt/acc/"):
                _, _, accname, pname = key.split("/", 3)
                opt_sd["accumulators"].setdefault(accname, {})[
                    _pname(pname)] = np.asarray(t._data)
            elif key.startswith("opt/master/"):
                opt_sd["master_weights"][_pname(key.split("/", 2)[2])] = \
                    np.asarray(t._data)
        # a full-state restore is authoritative: set_state_dict merges, so
        # accumulators/master-weights the checkpoint does NOT have (e.g.
        # restoring the step-0 save onto an optimizer that already stepped)
        # must be dropped or the replayed trajectory diverges
        train_step.optimizer._accumulators.clear()
        train_step.optimizer._master_weights.clear()
        train_step.optimizer.set_state_dict(opt_sd)
        if train_step.scaler is not None and host.get("scaler"):
            train_step.scaler.load_state_dict(host["scaler"])
        if scheduler is not None and host.get("scheduler"):
            scheduler.set_state_dict(host["scheduler"])
        # rebuild device state from the restored objects, install the saved
        # RNG carry, THEN restore the generator chain (the re-hydrate draws
        # one throwaway key)
        train_step.restore_resume_state(np.asarray(targets["rng/carry"]._data))
        default_generator().set_state(
            jnp.asarray(np.asarray(targets["rng/host"]._data), jnp.uint32))
        return {"step": int(manifest["step"]),
                "cursor": dict(host.get("cursor") or {}),
                "path": path,
                "resharded": bool(resharded),
                "saved_mesh": saved_mesh}
