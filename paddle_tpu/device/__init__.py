"""Device management (reference: python/paddle/device/ and
phi::Place, /root/reference/paddle/phi/common/place.h:57).

On TPU the device runtime (streams, events, allocators) is owned by
XLA/PJRT — the C++ analogue of the reference's DeviceContext stack ships
inside libtpu. This module provides the paddle-style identity layer: Places,
set_device/get_device, and synchronization."""

from __future__ import annotations

import jax

_CURRENT = None


class Place:
    def __init__(self, kind, device_id=0):
        self._kind = kind
        self._id = device_id

    def __repr__(self):
        return f"Place({self._kind}:{self._id})"

    def __eq__(self, other):
        return (isinstance(other, Place) and self._kind == other._kind
                and self._id == other._id)

    def is_cpu_place(self):
        return self._kind == "cpu"

    def is_tpu_place(self):
        return self._kind == "tpu"

    # compat: treat TPU as "the accelerator"
    def is_gpu_place(self):
        return self._kind == "tpu"


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, device_id=0):
        super().__init__("tpu", device_id)


# compat alias: code written against CUDAPlace runs on TPU
CUDAPlace = TPUPlace


class CUDAPinnedPlace(Place):
    """Pinned-host-memory place (reference: CUDAPinnedPlace). TPU analogue:
    plain host memory — jax device_put from numpy already uses pinned
    staging buffers internally."""

    def __init__(self):
        super().__init__("cpu_pinned", 0)

XPUPlace = TPUPlace
CustomPlace = TPUPlace


def _platform():
    # no try/except: a backend that fails to initialise is an error the
    # caller must see, never a reason to answer "cpu"
    return jax.devices()[0].platform


def on_tpu():
    """True when the default JAX backend is a TPU.  The ONE platform gate
    in the package: kernels, ``bench.py`` and ``chip_smoke.py`` all ask
    here, and ``"tpu"`` is the only accelerator platform name accepted."""
    return _platform() == "tpu"


def set_device(device):
    """paddle.device.set_device('tpu'|'cpu'|'tpu:0')."""
    global _CURRENT
    name = device.split(":")[0]
    if name in ("gpu", "cuda", "xpu"):
        name = "tpu" if _platform() != "cpu" else "cpu"
    _CURRENT = name
    return TPUPlace() if name == "tpu" else CPUPlace()


def get_device():
    return _current_place()


def _current_place():
    if _CURRENT is not None:
        return f"{_CURRENT}:0"
    return f"{_platform()}:0"


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []


def device_count():
    return jax.device_count()


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_tpu():
    return on_tpu()


def is_compiled_with_distribute():
    return True


def is_compiled_with_cinn():
    # XLA plays CINN's role and is always on
    return True


def synchronize(device=None):
    """Block until all launched work completes (reference:
    paddle.device.synchronize)."""
    for d in jax.live_arrays():
        d.block_until_ready()


class Event:
    """Host-visible completion marker (reference: paddle.device.Event).
    XLA's async dispatch has no user streams; record/synchronize map to
    array readiness."""

    def __init__(self, device=None, enable_timing=False):
        self._arrays = []
        import time
        self._time = None
        self._enable_timing = enable_timing

    def record(self, stream=None):
        import time
        self._arrays = list(jax.live_arrays())
        self._time = time.perf_counter()

    def synchronize(self):
        for a in self._arrays:
            a.block_until_ready()

    def query(self):
        return True

    def elapsed_time(self, end_event):
        return (end_event._time - self._time) * 1000.0


class Stream:
    """Compat shim: XLA:TPU exposes a single ordered execution stream."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        event.synchronize()

    def wait_stream(self, stream):
        synchronize()

    def record_event(self, event=None):
        e = event or Event()
        e.record()
        return e


def current_stream(device=None):
    return Stream(device)


def set_stream(stream):
    return stream


def stream_guard(stream):
    import contextlib
    return contextlib.nullcontext()


class cuda:
    """paddle.device.cuda compat namespace (maps onto the TPU runtime)."""
    Event = Event
    Stream = Stream

    @staticmethod
    def device_count():
        return jax.device_count()

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def current_stream(device=None):
        return Stream(device)

    @staticmethod
    def max_memory_allocated(device=None):
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0)

    @staticmethod
    def memory_allocated(device=None):
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("bytes_in_use", 0)

    @staticmethod
    def max_memory_reserved(device=None):
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0)

    @staticmethod
    def memory_reserved(device=None):
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("bytes_limit", 0)

    @staticmethod
    def empty_cache():
        pass
