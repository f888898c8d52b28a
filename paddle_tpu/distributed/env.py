"""Distributed environment: process bootstrap + the global device mesh.

Reference analogue: paddle.distributed.init_parallel_env
(python/paddle/distributed/parallel.py:945 — TCP store + NCCL comm contexts)
and fleet's HybridCommunicateGroup rank topology
(fleet/base/topology.py:178).

TPU-native: the JAX distributed runtime (coordination service) replaces the
TCPStore; the NCCL ring-per-axis machinery collapses into ONE
``jax.sharding.Mesh`` whose named axes are the parallelism dimensions.
Collectives are XLA ops partitioned over this mesh — there are no per-axis
communicators to manage.  Axis order follows the reference's topology order
pp→dp→sharding→sep→mp (topology.py:290) so that the innermost (most
communication-intensive) axis 'mp' maps to the fastest ICI links.
"""

from __future__ import annotations

import os

import jax
import numpy as np

_GLOBAL_MESH = None
_HYBRID_DEGREES = {"pp": 1, "dp": 1, "sharding": 1, "sep": 1, "mp": 1}

AXIS_ORDER = ("pp", "dp", "sharding", "sep", "mp")

#: mesh axis names a batch dimension shards over, wherever a mesh is
#: handed in directly rather than built here (``CompiledTrainStep(mesh=)``'s
#: default ``batch_axes``; the flash kernel's per-shard wrap)
DATA_AXES = ("dp", "sharding", "batch", "data")


_INITIALIZED = [False]


def is_initialized():
    """True once a mesh/parallel env has been built (reference:
    paddle.distributed.is_initialized — python/paddle/distributed/parallel.py)."""
    return _INITIALIZED[0] or _GLOBAL_MESH is not None


def reset_parallel_env():
    """Tear down the global mesh + hybrid degrees (test isolation; the
    reference equivalent is destroying the process groups)."""
    global _GLOBAL_MESH
    _GLOBAL_MESH = None
    for k in _HYBRID_DEGREES:
        _HYBRID_DEGREES[k] = 1
    _INITIALIZED[0] = False


def init_parallel_env():
    """Multi-host bootstrap. Under a launcher that sets JAX coordination env
    vars (or TPU pod metadata), jax.distributed.initialize connects the
    processes; single-process runs are a no-op."""
    if int(os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1 or \
            os.environ.get("COORDINATOR_ADDRESS"):
        # A failed bootstrap must be fatal: swallowing it would silently turn
        # an N-process job into N independent single-process runs (each
        # training on its own shard with no gradient sync — wrong results,
        # not a crash).  Reference: init_parallel_env raises on store/comm
        # init failure too (distributed/parallel.py:945).
        try:
            jax.distributed.initialize(
                coordinator_address=os.environ.get(
                    "COORDINATOR_ADDRESS",
                    os.environ.get("PADDLE_MASTER", None)),
                num_processes=int(os.environ.get("PADDLE_TRAINERS_NUM", "1")),
                process_id=int(os.environ.get("PADDLE_TRAINER_ID", "0")))
        except RuntimeError as e:
            if "already initialized" in str(e).lower():
                pass  # idempotent re-init (e.g. fleet.init after launcher)
            else:
                raise RuntimeError(
                    "jax.distributed.initialize failed for "
                    f"coordinator={os.environ.get('COORDINATOR_ADDRESS') or os.environ.get('PADDLE_MASTER')!r} "
                    f"num_processes={os.environ.get('PADDLE_TRAINERS_NUM')} "
                    f"process_id={os.environ.get('PADDLE_TRAINER_ID')}; "
                    "refusing to continue as a single-process run. Check the "
                    "coordinator address is reachable and the PADDLE_TRAINER_* "
                    "env vars set by the launcher.") from e
    _INITIALIZED[0] = True
    return ParallelEnv()


def get_rank(group=None):
    return jax.process_index()


def get_world_size(group=None):
    return jax.process_count()


class ParallelEnv:
    """reference: python/paddle/distributed/parallel.py ParallelEnv."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def device_id(self):
        return 0

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "127.0.0.1:0")

    @property
    def trainer_endpoints(self):
        return os.environ.get("PADDLE_TRAINER_ENDPOINTS", "").split(",")

    @property
    def nrings(self):
        return 1


def build_mesh(degrees=None, devices=None):
    """Build the global hybrid-parallel mesh.

    degrees: dict of axis -> degree over AXIS_ORDER.  Total must equal the
    device count (missing axes get degree 1; a single -1 axis absorbs the
    rest)."""
    global _GLOBAL_MESH, _HYBRID_DEGREES
    if devices is None:
        devices = np.asarray(jax.devices())
    n = len(devices)
    deg = {a: 1 for a in AXIS_ORDER}
    if degrees:
        deg.update({k: int(v) for k, v in degrees.items()})
    unknown = [a for a, v in deg.items() if v == -1]
    known = int(np.prod([v for v in deg.values() if v != -1]))
    if unknown:
        deg[unknown[0]] = n // known
    total = int(np.prod(list(deg.values())))
    if total != n:
        raise ValueError(f"mesh degrees {deg} product {total} != device "
                         f"count {n}")
    shape = tuple(deg[a] for a in AXIS_ORDER)
    try:
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except Exception:
        dev_array = np.asarray(devices).reshape(shape)
    _GLOBAL_MESH = jax.sharding.Mesh(dev_array, AXIS_ORDER)
    _HYBRID_DEGREES = deg
    return _GLOBAL_MESH


def get_mesh():
    return _GLOBAL_MESH


def set_mesh(mesh):
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh
    return mesh


def hybrid_degrees():
    return dict(_HYBRID_DEGREES)


def data_axes():
    """Axes over which the batch is sharded (dp + sharding fused, like the
    reference's fused dp_sharding groups)."""
    axes = [a for a in ("dp", "sharding") if _HYBRID_DEGREES.get(a, 1) > 1]
    return tuple(axes) if axes else ("dp",)
