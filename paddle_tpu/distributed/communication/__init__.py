"""Eager collective API (reference: python/paddle/distributed/communication/
— all_reduce.py:20 etc., backed by ProcessGroupNCCL).

TPU-native: inside compiled (pjit/shard_map) code, collectives are jax.lax
ops and GSPMD insertions — this module provides the *eager* API shape.  On a
sharded Tensor it applies the collective via shard_map over the global mesh;
on a single-process replicated tensor the ops are identities (world=1) or
multihost psums via jax.  Async semantics: XLA dispatch is async by nature, so
every call returns a completed-on-dispatch task object (``wait`` blocks)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.tensor import Tensor
from ...profiler import counters as _counters
from ...profiler import host_tracer as _tracer
from ..env import get_mesh, get_world_size


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """reference: distributed/communication/group.py Group."""

    def __init__(self, rank=0, ranks=None, axis_names=None, id=0):
        self.rank = rank
        self.ranks = ranks if ranks is not None else [0]
        self.axis_names = axis_names  # mesh axes this group spans
        self.id = id

    @property
    def nranks(self):
        return len(self.ranks)

    @property
    def world_size(self):
        return len(self.ranks)

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    process_group = property(lambda self: self)


_GROUPS = {}
_GROUP_COUNTER = [0]


def new_group(ranks=None, backend=None, timeout=None):
    """reference: distributed/collective.py:186 new_group."""
    _GROUP_COUNTER[0] += 1
    g = Group(rank=0 if not ranks or get_rank_in(ranks) < 0 else
              get_rank_in(ranks),
              ranks=ranks or list(range(get_world_size())),
              id=_GROUP_COUNTER[0])
    _GROUPS[g.id] = g
    return g


def get_rank_in(ranks):
    from ..env import get_rank
    r = get_rank()
    return ranks.index(r) if r in ranks else -1


def get_group(gid=0):
    return _GROUPS.get(gid)


def is_initialized():
    from ..env import is_initialized as _env_init
    return _env_init()


class _Task:
    def __init__(self, value=None):
        self._value = value

    def wait(self):
        if self._value is not None:
            self._value.block_until_ready()
        return True

    def is_completed(self):
        return True


def _nranks(group):
    return group.nranks if group is not None else get_world_size()


def _apply_collective(tensor, per_shard_fn, identity_ok=True):
    """Run an eager collective.  With a >1-axis mesh and a sharded input,
    wrap in shard_map; degenerate (single-participant) collectives are
    identities."""
    return per_shard_fn(tensor)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    n = _nranks(group)
    if n <= 1:
        return _Task(tensor._data)
    mesh = get_mesh()
    axes = group.axis_names if group is not None and group.axis_names else None
    if mesh is not None and axes:
        from jax.sharding import PartitionSpec as P

        def body(x):
            if op in (ReduceOp.SUM, ReduceOp.AVG):
                r = jax.lax.psum(x, axes)
                if op == ReduceOp.AVG:
                    r = r / n
                return r
            if op == ReduceOp.MAX:
                return jax.lax.pmax(x, axes)
            if op == ReduceOp.MIN:
                return jax.lax.pmin(x, axes)
            raise ValueError(op)
        sm = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False)
        tensor._data = sm(tensor._data)
        return _Task(tensor._data)
    # multihost replicated eager allreduce over the group members
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(tensor._data)
    ranks, gr = _group_members(group)
    if gr < 0:
        return _Task(tensor._data)
    members = jnp.asarray(gathered)[jnp.asarray(ranks)]
    tensor._data = _reduce_stacked(members, op)
    return _Task(tensor._data)


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    n = _nranks(group)
    if n <= 1:
        tensor_list.append(Tensor._wrap(tensor._data))
        return _Task(tensor._data)
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(tensor._data)
    for i in range(gathered.shape[0]):
        tensor_list.append(Tensor._wrap(gathered[i]))
    return _Task(tensor._data)


def all_gather_object(object_list, obj, group=None):
    n = _nranks(group)
    if n <= 1:
        object_list.append(obj)
        return
    raise NotImplementedError("object gather across hosts")


def broadcast(tensor, src=0, group=None, sync_op=True):
    n = _nranks(group)
    if n <= 1:
        return _Task(tensor._data)
    from jax.experimental import multihost_utils
    tensor._data = multihost_utils.broadcast_one_to_all(
        tensor._data, is_source=(get_world_size() == 1 or
                                 jax.process_index() == src))
    return _Task(tensor._data)


def _reduce_stacked(stacked, op):
    if op == ReduceOp.SUM:
        return jnp.sum(stacked, axis=0)
    if op == ReduceOp.AVG:
        return jnp.mean(stacked, axis=0)
    if op == ReduceOp.MAX:
        return jnp.max(stacked, axis=0)
    if op == ReduceOp.MIN:
        return jnp.min(stacked, axis=0)
    if op == ReduceOp.PROD:
        return jnp.prod(stacked, axis=0)
    raise ValueError(f"unsupported reduce op {op!r}")


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """Reduce to ONE rank over the GROUP members: the result is defined only
    at `dst`; every other rank's tensor is left unchanged (reference
    semantics, communication/reduce.py — previously this wrongly aliased
    all_reduce, placing an all-ranks reduction on every rank)."""
    n = _nranks(group)
    if n <= 1:
        return _Task(tensor._data)
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(tensor._data)
    ranks, gr = _group_members(group)
    if gr < 0:
        return _Task(tensor._data)
    members = jnp.asarray(gathered)[jnp.asarray(ranks)]
    if jax.process_index() == dst:
        tensor._data = _reduce_stacked(members, op)
    return _Task(tensor._data)


def _group_members(group):
    """(ranks, my_group_rank).  Eager subgroup collectives are built on
    multihost_utils primitives, which are collective over ALL processes —
    so every process (member or not) must call; non-members contribute
    zeros and keep their tensor unchanged."""
    n_world = get_world_size()
    ranks = (list(group.ranks) if group is not None and group.ranks
             else list(range(n_world)))
    me = jax.process_index()
    return ranks, (ranks.index(me) if me in ranks else -1)


def reduce_scatter(tensor, tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """Each group member contributes `nranks` chunks; member r receives the
    reduction of every member's chunk r (reference:
    communication/reduce_scatter.py).  Eager path: host-level allgather +
    local reduction — correct on single- and multi-host; compiled code
    should rely on GSPMD's reduce-scatter."""
    n = _nranks(group)
    if isinstance(tensor_list, (list, tuple)):
        srcs = [s._data for s in tensor_list]
    else:
        # single-tensor form: the input is the concatenation of the n
        # chunks along dim 0 (reference stream/reduce_scatter.py)
        srcs = (list(jnp.split(tensor_list._data, n, axis=0)) if n > 1
                else [tensor_list._data])
    if n <= 1:
        tensor._data = srcs[0]
        return _Task(tensor._data)
    if len(srcs) != n:
        raise ValueError(
            f"reduce_scatter needs exactly nranks={n} input chunks, got "
            f"{len(srcs)}")
    from jax.experimental import multihost_utils
    stacked = jnp.stack(srcs)                              # [n, ...]
    gathered = multihost_utils.process_allgather(stacked)  # [world, n, ...]
    ranks, gr = _group_members(group)
    if gr < 0:
        return _Task(tensor._data)
    members = jnp.asarray(gathered)[jnp.asarray(ranks)]    # [n, n, ...]
    red = _reduce_stacked(members, op)                     # [n, ...]
    tensor._data = jnp.asarray(red[gr])
    return _Task(tensor._data)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """Global rank `src` distributes one chunk to each group member
    (reference: communication/scatter.py)."""
    n = _nranks(group)
    if n <= 1:
        if tensor_list:
            tensor._data = tensor_list[0]._data
        return _Task(tensor._data)
    from jax.experimental import multihost_utils
    me = jax.process_index()
    if me == src and not tensor_list:
        raise ValueError(
            "scatter: the source rank must provide tensor_list (one chunk "
            "per group member)")
    if tensor_list:
        stacked = jnp.stack([t._data for t in tensor_list])
    else:
        # non-source ranks may omit tensor_list; shape must still match
        stacked = jnp.zeros((n,) + tuple(tensor._data.shape),
                            tensor._data.dtype)
    data = multihost_utils.broadcast_one_to_all(stacked,
                                                is_source=(me == src))
    ranks, gr = _group_members(group)
    if gr < 0:
        return _Task(tensor._data)
    tensor._data = jnp.asarray(data[gr])
    return _Task(tensor._data)


def all_to_all(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    """out[i] on member r = in[r] on member i (reference:
    communication/all_to_all.py)."""
    n = _nranks(group)
    if n <= 1:
        out_tensor_list.extend(Tensor._wrap(t._data) for t in in_tensor_list)
        return _Task(None)
    from jax.experimental import multihost_utils
    stacked = jnp.stack([t._data for t in in_tensor_list])  # [n, ...]
    gathered = multihost_utils.process_allgather(stacked)   # [world, n, ...]
    ranks, gr = _group_members(group)
    if gr < 0:
        return _Task(None)
    members = jnp.asarray(gathered)[jnp.asarray(ranks)]     # [n, n, ...]
    out_tensor_list.extend(Tensor._wrap(jnp.asarray(members[i][gr]))
                           for i in range(n))
    return _Task(None)


def send(tensor, dst=0, group=None, sync_op=True):
    """Eager point-to-point send (reference: communication/send.py).

    Host-level implementation over the global allgather primitive, which
    is collective over ALL processes — safe exactly when every process is
    in a matched send/recv pair, i.e. world size 2.  Larger worlds must
    use the compiled path (lax.ppermute in distributed/pipeline.py), where
    p2p is a real neighbor exchange."""
    n = _nranks(group)
    if n <= 1:
        return _Task(tensor._data)
    if get_world_size() > 2:
        raise NotImplementedError(
            "eager send/recv is supported for world size 2 (both processes "
            "rendezvous); with more processes use the compiled pipeline "
            "path (lax.ppermute) or batch the transfer as a collective")
    from jax.experimental import multihost_utils
    multihost_utils.process_allgather(tensor._data)  # rendezvous w/ recv
    return _Task(tensor._data)


def recv(tensor, src=0, group=None, sync_op=True):
    """Eager point-to-point receive (see send)."""
    n = _nranks(group)
    if n <= 1:
        return _Task(tensor._data)
    if get_world_size() > 2:
        raise NotImplementedError(
            "eager send/recv is supported for world size 2; see send()")
    from jax.experimental import multihost_utils
    gathered = multihost_utils.process_allgather(tensor._data)
    tensor._data = jnp.asarray(gathered)[src]
    return _Task(tensor._data)


def isend(tensor, dst=0, group=None):
    return send(tensor, dst, group)


def irecv(tensor, src=0, group=None):
    return recv(tensor, src, group)


class P2POp:
    def __init__(self, op, tensor, peer, group=None):
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    tasks = []
    for op in p2p_op_list:
        tasks.append(op.op(op.tensor, op.peer, op.group))
    return tasks


def barrier(group=None):
    if get_world_size() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("paddle_tpu_barrier")


def stream_all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True,
                      use_calc_stream=False):
    return all_reduce(tensor, op, group, sync_op)


# ---------------------------------------------------------------------------
# Observability: every eager collective bumps dist.collectives + dist.<op>
# in profiler.counters and opens a host-tracer span.  (stream_all_reduce /
# isend / irecv delegate to the wrapped primitives, so each logical
# collective is counted exactly once.)
# ---------------------------------------------------------------------------
def _instrumented(fn):
    import functools
    cname = "dist." + fn.__name__

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        _counters.inc("dist.collectives")
        # host-issued collective dispatches; GSPMD-inserted collectives
        # inside a compiled mesh step are NOT host launches and stay at 0
        # (the zero-host-sync invariant check_counters.py gates on)
        _counters.inc("dist.collective_launches")
        _counters.inc(cname)
        with _tracer.span(cname):
            return fn(*args, **kwargs)
    return wrapper


for _n in ("all_reduce", "all_gather", "all_gather_object", "broadcast",
           "reduce", "reduce_scatter", "scatter", "all_to_all", "send",
           "recv", "barrier"):
    globals()[_n] = _instrumented(globals()[_n])
del _n


class stream:
    """paddle.distributed.stream.* variants (reference:
    communication/stream/) — XLA has one ordered stream; these alias the
    defaults."""
    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    broadcast = staticmethod(broadcast)
    reduce = staticmethod(reduce)
    reduce_scatter = staticmethod(reduce_scatter)
    scatter = staticmethod(scatter)
    all_to_all = staticmethod(all_to_all)
    send = staticmethod(send)
    recv = staticmethod(recv)
