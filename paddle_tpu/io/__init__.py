"""Data pipeline (reference: python/paddle/io/ — Dataset/DataLoader,
dataloader_iter.py multiprocess workers + LoDTensorBlockingQueue async
staging).

TPU-native: the host pipeline produces numpy batches on background threads
(prefetch queue = the BlockingQueue analogue); device transfer happens once
per step (jnp.asarray) and overlaps with compute thanks to XLA async dispatch.
``DevicePrefetcher`` closes the remaining gap: it issues ``jax.device_put``
for batch N+1 while step N is still executing (depth-2 double buffer), so the
host->device copy never sits on the step critical path.
"""

from __future__ import annotations

import itertools

import threading
import time as _time
import weakref

import numpy as np

from ..core.tensor import Tensor
from ..profiler import counters as _counters
from ..profiler import host_tracer as _trace
from ..profiler import metrics as _metrics


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset does not support indexing")

    def __len__(self):
        raise RuntimeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            item = d[idx]
            out.extend(item if isinstance(item, (tuple, list)) else [item])
        return tuple(out)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        return itertools.chain(*self.datasets)


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = np.cumsum([len(d) for d in self.datasets]).tolist()

    def __len__(self):
        return self.cum[-1]

    def __getitem__(self, idx):
        if idx < 0:
            idx += len(self)
        di = int(np.searchsorted(self.cum, idx, side="right"))
        prev = 0 if di == 0 else self.cum[di - 1]
        return self.datasets[di][idx - prev]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    lengths = list(lengths)
    if all(isinstance(l, float) for l in lengths) and abs(sum(lengths) - 1) < 1e-6:
        n = len(dataset)
        lengths = [int(np.floor(n * f)) for f in lengths]
        lengths[-1] = n - sum(lengths[:-1])
    if sum(lengths) != len(dataset):
        raise ValueError("sum of lengths != dataset size")
    perm = np.random.permutation(len(dataset)).tolist()
    out, ofs = [], 0
    for l in lengths:
        out.append(Subset(dataset, perm[ofs:ofs + l]))
        ofs += l
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num = num_samples

    @property
    def num_samples(self):
        return self._num if self._num is not None else len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[: self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(
            weights.numpy() if isinstance(weights, Tensor) else weights,
            dtype=np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(p), self.num_samples,
                               replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Shards indices across data-parallel ranks (reference:
    io/dataloader/batch_sampler.py DistributedBatchSampler)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        from ..distributed import get_rank, get_world_size
        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas if num_replicas is not None else \
            get_world_size()
        self.local_rank = rank if rank is not None else get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(np.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        indices = np.arange(n)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            rng.shuffle(indices)
        indices = np.concatenate(
            [indices, indices[: self.total_size - n]])
        indices = indices[self.local_rank::self.nranks]
        batch = []
        for idx in indices:
            batch.append(int(idx))
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        from .native import collate_stack
        return Tensor(collate_stack(batch))
    if isinstance(sample, Tensor):
        from ..tensor.manipulation import stack
        return stack(batch, 0)
    if isinstance(sample, (int, np.integer)):
        return Tensor(np.asarray(batch, np.int64))
    if isinstance(sample, (float, np.floating)):
        return Tensor(np.asarray(batch, np.float32))
    if isinstance(sample, (list, tuple)):
        return tuple(default_collate_fn([b[i] for b in batch])
                     for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    return batch


class _PrefetchIter:
    """Background-thread prefetcher — the BlockingQueue analogue
    (reference: io/dataloader/dataloader_iter.py:365 multiprocess loop)."""

    def __init__(self, loader, index_iter):
        self._loader = loader
        self._index_iter = index_iter
        self._index_lock = threading.Lock()
        self._stop = threading.Event()
        self._seq = itertools.count()
        self._results = {}
        self._cv = threading.Condition()
        self._next_emit = 0
        n = max(1, loader.num_workers)
        self._max_pending = max(2, loader.prefetch_factor) * n
        self._threads = []
        # Start workers only after ALL state above exists — they touch
        # _cv/_results immediately (round-1 deadlock: workers raced a
        # partially-constructed self, died on AttributeError, and the
        # consumer waited forever).  Workers hold only a weakref to self so
        # an abandoned iterator is collectable and its workers exit.
        wref = weakref.ref(self)
        for _ in range(n):
            t = threading.Thread(target=_PrefetchIter._worker_main,
                                 args=(wref,), daemon=True)
            t.start()
            self._threads.append(t)

    @staticmethod
    def _worker_main(wref):
        strong = wref()
        if strong is None:
            return
        # long-lived primitives; none of these keep the iterator alive
        cv = strong._cv
        stop = strong._stop
        index_lock = strong._index_lock
        index_iter = strong._index_iter
        seq_counter = strong._seq
        del strong
        try:
            while not stop.is_set():
                sampler_err = None
                with index_lock:
                    try:
                        indices = next(index_iter)
                    except StopIteration:
                        break
                    except Exception as e:  # broken batch_sampler: deliver,
                        sampler_err = e     # don't silently truncate the epoch
                    seq = next(seq_counter)

                # backpressure: at most _max_pending undelivered batches.
                # Predicate re-resolves the weakref so a blocked worker never
                # pins an abandoned iterator.
                def _ready():
                    st = wref()
                    return (st is None or stop.is_set()
                            or seq - st._next_emit < st._max_pending)

                with cv:
                    while not cv.wait_for(_ready, timeout=0.5):
                        pass
                s = wref()
                if s is None or stop.is_set():
                    return
                if sampler_err is not None:
                    batch = sampler_err
                else:
                    try:
                        batch = s._fetch(indices)
                    except Exception as e:  # propagate to the consumer
                        batch = e
                with cv:
                    s._results[seq] = batch
                    cv.notify_all()
                if isinstance(batch, Exception):
                    break
                del s
        finally:
            # unconditional: a worker dying for ANY reason must never leave
            # the consumer blocked
            s = wref()
            if s is not None:
                with cv:
                    s._results.setdefault("done", None)
                    cv.notify_all()

    def _fetch(self, indices):
        with _trace.span("io.reader"):
            data = [self._loader.dataset[i] for i in indices]
            cf = self._loader.collate_fn or default_collate_fn
            return cf(data)

    def __next__(self):
        t0 = _time.perf_counter_ns()
        with self._cv:
            while True:
                if self._next_emit in self._results:
                    batch = self._results.pop(self._next_emit)
                    self._next_emit += 1
                    self._cv.notify_all()  # wake backpressured workers
                    # time this consumer spent blocked on the worker queue
                    _metrics.observe("io.queue_wait_ns",
                                     _time.perf_counter_ns() - t0,
                                     unit="ns", sum_counter=True)
                    if isinstance(batch, Exception):
                        raise batch
                    return batch
                if "done" in self._results and not any(
                        isinstance(k, int) and k >= self._next_emit
                        for k in self._results):
                    alive = any(t.is_alive() for t in self._threads)
                    if not alive:
                        raise StopIteration
                self._cv.wait(timeout=0.05)

    def __iter__(self):
        return self

    def __del__(self):
        self._stop.set()
        with self._cv:
            self._cv.notify_all()  # wake backpressured workers to exit


class DataLoader:
    """reference: python/paddle/io/reader.py:216 DataLoader."""

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self._is_iterable = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = getattr(batch_sampler, "batch_size", batch_size)
        elif self._is_iterable:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)
            self.batch_size = batch_size

    def __len__(self):
        if self._is_iterable:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)

    def _iter_iterable(self):
        cf = self.collate_fn or default_collate_fn
        batch = []
        for item in self.dataset:
            batch.append(item)
            if len(batch) == self.batch_size:
                yield cf(batch)
                batch = []
        if batch and not getattr(self, "drop_last", False):
            yield cf(batch)

    def __iter__(self):
        if self._is_iterable:
            return self._iter_iterable()
        index_iter = iter(self.batch_sampler)
        if self.num_workers == 0:
            def gen():
                cf = self.collate_fn or default_collate_fn
                for indices in index_iter:
                    with _trace.span("io.reader"):
                        batch = cf([self.dataset[i] for i in indices])
                    yield batch
            return gen()
        return _PrefetchIter(self, index_iter)


class DevicePrefetcher:
    """Depth-``depth`` device double buffer over any batch iterable.

    Wrap a DataLoader (or any iterable yielding Tensors / nested
    tuples/lists/dicts of Tensors or numpy arrays) and iterate the wrapper
    instead: each incoming host batch is pushed through ``jax.device_put``
    the moment the loader produces it, and handed to the consumer
    ``depth - 1`` batches later.  Because jax dispatch is async, the
    transfer for batch N+1 is in flight while the train step for batch N is
    still executing — the copy never blocks the step critical path.  Batch
    values are bit-identical to the plain loader's; only placement/timing
    changes.

        loader = paddle_tpu.io.DataLoader(ds, batch_size=64)
        for x, y in paddle_tpu.io.DevicePrefetcher(loader, depth=2):
            loss = compiled_step(x, y)

    Resume cursor (``resilience.CheckpointManager``): ``consumed`` counts
    batches *delivered to the consumer* (buffered-but-undelivered batches
    don't count — they were never trained on), so it is the exact
    data-iterator offset to checkpoint.  Passing it back as
    ``start_offset`` on a fresh prefetcher over a deterministic loader
    replays the epoch to that position: skipped batches are pulled from the
    loader but neither staged on device nor delivered, and are counted
    under ``io.skipped_batches``.

    Multi-chip: pass ``sharding`` (a ``jax.sharding.NamedSharding``,
    typically ``NamedSharding(mesh, P("dp"))``) instead of ``device`` and
    each batch leaf is placed data-parallel across the mesh in ONE sharded
    ``jax.device_put`` — no per-shard host loop.  Leaves whose batch dim
    does not divide the data axes (or whose rank is below the spec) degrade
    to replicated-on-mesh so the device set stays uniform.  Sharded bytes
    are tallied under ``dist.device_put_sharded_bytes``.
    """

    def __init__(self, loader, depth=2, device=None, start_offset=0,
                 sharding=None):
        self.loader = loader
        self.depth = max(1, int(depth))
        self.device = device
        self.sharding = sharding
        self.start_offset = max(0, int(start_offset))
        self.consumed = self.start_offset

    def __len__(self):
        return max(0, len(self.loader) - self.start_offset)

    def _target(self, shape):
        """Placement target for one batch leaf: the configured sharding
        (spec degraded to replicated when it doesn't fit the leaf), else
        the configured device."""
        if self.sharding is None:
            return self.device, False
        spec = getattr(self.sharding, "spec", None)
        mesh = getattr(self.sharding, "mesh", None)
        if spec is None or mesh is None:
            return self.sharding, True
        from jax.sharding import NamedSharding
        from ..distributed.sharding_utils import validate_spec
        return NamedSharding(mesh, validate_spec(spec, shape, mesh,
                                                 quiet=True)), True

    def _put(self, arr):
        import jax
        target, sharded = self._target(arr.shape)
        _counters.inc("io.device_put_calls")
        _counters.inc("io.device_put_bytes", int(arr.nbytes))
        out = jax.device_put(arr, target)
        if sharded:
            _counters.inc("dist.device_put_sharded_bytes", int(arr.nbytes))
        return out

    def _stage(self, batch):
        if isinstance(batch, Tensor):
            return Tensor._wrap(self._put(batch._data))
        if isinstance(batch, (np.ndarray, np.generic)):
            return Tensor._wrap(self._put(np.asarray(batch)))
        if isinstance(batch, (list, tuple)):
            return type(batch)(self._stage(b) for b in batch)
        if isinstance(batch, dict):
            return {k: self._stage(v) for k, v in batch.items()}
        return batch

    def __iter__(self):
        from collections import deque
        buf = deque()
        it = iter(self.loader)
        self.consumed = self.start_offset
        if self.start_offset:
            # replay-to-offset: drain skipped batches host-side only — no
            # device_put, no staging, just advancing the loader cursor
            skipped = 0
            for _ in range(self.start_offset):
                try:
                    next(it)
                except StopIteration:
                    break
                skipped += 1
            _counters.inc("io.skipped_batches", skipped)
        while True:
            with _trace.span("io.prefetcher"):
                t0 = _time.perf_counter_ns()
                try:
                    batch = next(it)
                except StopIteration:
                    break
                wait = _time.perf_counter_ns() - t0
                # reader wait is a true stall only when the device buffer is
                # dry — otherwise the transfer already in flight hides it
                _counters.inc("io.reader_ns", wait)
                if not buf:
                    _metrics.observe("io.prefetch_stall_ns", wait,
                                     unit="ns", sum_counter=True)
                with _trace.span("io.device_put"):
                    staged = self._stage(batch)
                buf.append(staged)
            if len(buf) >= self.depth:
                self.consumed += 1
                yield buf.popleft()
        while buf:
            self.consumed += 1
            yield buf.popleft()


class Window(tuple):
    """A window of ``k`` training batches stacked along a new leading axis,
    ready for fused multi-step dispatch (``jit.CompiledTrainStep`` with
    ``fused_steps=k``).

    A ``Window`` IS the tuple of stacked step-arguments (``step(*w)``
    unpacks them), carrying the window length as ``.k`` so partial tail
    windows (loader length not a multiple of k) stay self-describing —
    the compiled step falls back to single-step dispatch for them instead
    of dropping or padding batches.
    """

    def __new__(cls, args, k):
        self = tuple.__new__(cls, tuple(args))
        self.k = int(k)
        return self


class StackingPrefetcher:
    """Window feeder for fused multi-step dispatch: stages the next ``k``
    batches on device (through a ``DevicePrefetcher``) and stacks them into
    one ``Window`` while the current window is still executing.

    The stack itself (``jnp.stack`` over already-staged device arrays) is
    async XLA work, so neither the host->device copies nor the stacking sit
    on the step critical path.  Batch values are bit-identical to the plain
    loader's; only placement/grouping changes.

        loader = paddle_tpu.io.DataLoader(ds, batch_size=64)
        step = jit.CompiledTrainStep(model, loss_fn, opt, fused_steps=4)
        for w in paddle_tpu.io.StackingPrefetcher(loader, k=4):
            losses = step(*w)      # ONE XLA launch for 4 steps

    Drain edge: when the loader length is not a multiple of ``k`` (or a
    trailing batch changes shape, e.g. a drop_last=False remainder batch),
    the leftover batches are emitted as a partial ``Window`` (``w.k < k``)
    — never dropped, never padded; the compiled step runs them as single
    steps.

    Multi-chip: pass ``sharding`` (the per-batch data-parallel
    ``NamedSharding``, e.g. ``NamedSharding(mesh, P("dp"))``) and batches
    stage sharded (see ``DevicePrefetcher``); the stacked window is then
    re-pinned to ``P(None, dp...)`` — window axis replicated, batch axis
    sharded — which is exactly the xs layout the mesh-native fused step
    slices per scan iteration.
    """

    def __init__(self, loader, k, depth=None, device=None, start_offset=0,
                 sharding=None):
        self.loader = loader
        self.k = max(1, int(k))
        # double-buffer in window units: the next window's batches stage
        # while the current window runs
        depth = 2 * self.k if depth is None else max(1, int(depth))
        self.start_offset = max(0, int(start_offset))
        self.sharding = sharding
        self._pref = DevicePrefetcher(loader, depth=depth, device=device,
                                      start_offset=self.start_offset,
                                      sharding=sharding)
        # resume cursor in UNDERLYING batches (k per full window), counted
        # when a window is delivered — matches DevicePrefetcher.consumed
        self.consumed = self.start_offset

    def __len__(self):
        n = max(0, len(self.loader) - self.start_offset)
        return (n + self.k - 1) // self.k

    @staticmethod
    def _spec(batch):
        if isinstance(batch, Tensor):
            return ("t", tuple(batch._data.shape), str(batch._data.dtype))
        if isinstance(batch, (list, tuple)):
            return tuple(StackingPrefetcher._spec(b) for b in batch)
        if isinstance(batch, dict):
            return {k: StackingPrefetcher._spec(v)
                    for k, v in sorted(batch.items())}
        return ("py", type(batch).__name__)

    def _restage(self, arr):
        """Pin a K-stacked window leaf to the window version of the batch
        sharding (batch spec shifted right past the new leading window
        axis): ``jnp.stack`` over sharded inputs lets the compiler pick an
        arbitrary output layout, and the fused step needs the stable
        ``P(None, dp...)`` one."""
        if self.sharding is None:
            return arr
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        spec = getattr(self.sharding, "spec", None)
        mesh = getattr(self.sharding, "mesh", None)
        if spec is None or mesh is None:
            return jax.device_put(arr, self.sharding)
        from ..distributed.sharding_utils import validate_spec
        wspec = validate_spec(PartitionSpec(None, *spec), arr.shape, mesh,
                              quiet=True)
        out = jax.device_put(arr, NamedSharding(mesh, wspec))
        _counters.inc("dist.device_put_sharded_bytes", int(arr.nbytes))
        return out

    def _stack(self, items):
        import jax.numpy as jnp
        first = items[0]
        if isinstance(first, Tensor):
            return Tensor._wrap(self._restage(
                jnp.stack([t._data for t in items])))
        if isinstance(first, (list, tuple)):
            return type(first)(self._stack([b[i] for b in items])
                               for i in range(len(first)))
        if isinstance(first, dict):
            return {k: self._stack([b[k] for b in items])
                    for k in first}
        return Tensor._wrap(self._restage(
            jnp.stack([jnp.asarray(x) for x in items])))

    def _emit(self, batches):
        with _trace.span("io.stack_window"):
            _counters.inc("io.stack_windows")
            _counters.inc("io.stack_batches", len(batches))
            stacked = self._stack(batches)
            args = stacked if isinstance(stacked, tuple) else (stacked,)
            self.consumed += len(batches)
            return Window(args, len(batches))

    def __iter__(self):
        pending = []
        spec0 = None
        self.consumed = self.start_offset
        for staged in self._pref:
            s = self._spec(staged)
            if pending and s != spec0:
                # shape/structure break (e.g. a drop_last=False remainder
                # batch): flush what accumulated as a partial window
                yield self._emit(pending)
                pending = []
            if not pending:
                spec0 = s
            pending.append(staged)
            if len(pending) == self.k:
                yield self._emit(pending)
                pending = []
        if pending:
            # loader length not a multiple of k: partial tail window
            yield self._emit(pending)


def get_worker_info():
    return None


class SubsetRandomSampler(Sampler):
    """Sample a fixed index subset in random order (reference:
    io/sampler.py SubsetRandomSampler)."""

    def __init__(self, indices):
        self.indices = list(indices)

    def __iter__(self):
        import numpy as np
        order = np.random.permutation(len(self.indices))
        return iter([self.indices[i] for i in order])

    def __len__(self):
        return len(self.indices)
