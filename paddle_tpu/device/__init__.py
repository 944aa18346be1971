"""paddle_tpu.device — device management + memory stats.

TPU-native re-design of the reference device package
(reference: python/paddle/device/__init__.py set_device/get_device,
device/cuda/__init__.py memory_allocated:261, max_memory_allocated:195,
synchronize:78, device_count:111, get_device_properties:387; C++
AllocatorFacade memory/allocation/allocator_facade.h:44 and stats
memory/stats.h).

The reference's allocator owns GPU memory, so stats come from its own
counters. On TPU, XLA/PJRT owns HBM; stats come straight from the PJRT
device (`Device.memory_stats()`). The `cuda` submodule name is kept as
an alias of the accelerator module for source compatibility — its
functions operate on the current accelerator (TPU) device.
"""
import jax

__all__ = [
    "set_device", "get_device", "get_all_device_type",
    "get_all_custom_device_type", "get_available_device",
    "get_available_custom_device", "device_count", "synchronize",
    "memory_allocated", "max_memory_allocated", "memory_reserved",
    "max_memory_reserved", "empty_cache", "get_device_properties",
    "get_device_name", "is_compiled_with_cuda", "is_compiled_with_xpu",
    "is_compiled_with_npu", "is_compiled_with_ipu",
    "is_compiled_with_custom_device", "cuda", "Stream", "Event",
    "stream_guard", "current_stream",
]

_current = None


def _accel_devices():
    devs = jax.devices()
    accel = [d for d in devs if d.platform != "cpu"]
    return accel or devs


def set_device(device):
    """'tpu', 'tpu:0', 'cpu', or the reference's 'gpu:0' (mapped to the
    accelerator). An index past the last device is an error, not a
    clamp: 'tpu:9' on a 4-chip host names a chip that does not exist."""
    global _current
    name = str(device).lower()
    kind, _, idx = name.partition(":")
    idx = int(idx) if idx else 0
    if kind in ("cpu",):
        pool = [d for d in jax.devices() if d.platform == "cpu"] or \
            jax.devices()
    else:  # tpu / gpu / xpu / custom names all mean "the accelerator"
        pool = _accel_devices()
    if not 0 <= idx < len(pool):
        raise ValueError(
            f"set_device({device!r}): index {idx} out of range — "
            f"{len(pool)} {pool[0].platform} device(s) visible")
    _current = pool[idx]
    jax.config.update("jax_default_device", _current)
    return _current


def _current_device():
    if _current is not None:
        return _current
    return _accel_devices()[0]


def get_device():
    d = _current_device()
    plat = "cpu" if d.platform == "cpu" else d.platform
    return f"{plat}:{d.id}"


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return [p for p in get_all_device_type() if p not in ("cpu", "gpu")]


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [s for s in get_available_device()
            if not s.startswith(("cpu", "gpu"))]


def device_count():
    return len(_accel_devices())


def synchronize(device=None):
    """Block until all queued work on the device is done (reference
    cuda.synchronize:78). XLA equivalent: fence on a trivial committed
    computation."""
    d = _resolve(device)
    jax.device_put(0, d).block_until_ready()


def _resolve(device):
    if device is None:
        return _current_device()
    if isinstance(device, int):
        return _accel_devices()[device]
    if isinstance(device, str):
        return set_device(device)
    return device


def _stats(device):
    d = _resolve(device)
    try:
        return d.memory_stats() or {}
    except Exception:
        return {}


def memory_allocated(device=None):
    """Bytes currently allocated on the device (reference cuda
    memory_allocated:261 ← DEVICE_MEMORY_STAT Allocated; here PJRT
    bytes_in_use)."""
    return int(_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None):
    return int(_stats(device).get("peak_bytes_in_use",
                                  memory_allocated(device)))


def memory_reserved(device=None):
    s = _stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None):
    s = _stats(device)
    return int(s.get("bytes_limit", max_memory_allocated(device)))


def empty_cache():
    """XLA owns the buffer pool; nothing to flush (kept for parity)."""


def get_device_properties(device=None):
    d = _resolve(device)

    class _Props:
        name = getattr(d, "device_kind", d.platform)
        total_memory = int(_stats(device).get("bytes_limit", 0))
        multi_processor_count = len(_accel_devices())
        major, minor = 0, 0

        def __repr__(self):
            return (f"_DeviceProperties(name='{self.name}', "
                    f"total_memory={self.total_memory})")

    return _Props()


def get_device_name(device=None):
    return get_device_properties(device).name


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_custom_device(device_type=None):
    return any(d.platform not in ("cpu", "gpu") for d in jax.devices())


class Stream:
    """XLA schedules its own streams; kept as a no-op shim for source
    compatibility (reference cuda.Stream)."""

    def __init__(self, device=None, priority=2):
        self.device = _resolve(device)

    def synchronize(self):
        synchronize(self.device)


class Event:
    def __init__(self, enable_timing=False, blocking=False,
                 interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def synchronize(self):
        synchronize(None)

    def query(self):
        return True


import contextlib as _contextlib


@_contextlib.contextmanager
def stream_guard(stream):
    yield


def current_stream(device=None):
    return Stream(device)


class _CudaAlias:
    """paddle.device.cuda.* source-compat namespace: the functions act on
    the current accelerator (TPU)."""

    device_count = staticmethod(device_count)
    synchronize = staticmethod(synchronize)
    memory_allocated = staticmethod(memory_allocated)
    max_memory_allocated = staticmethod(max_memory_allocated)
    memory_reserved = staticmethod(memory_reserved)
    max_memory_reserved = staticmethod(max_memory_reserved)
    empty_cache = staticmethod(empty_cache)
    get_device_properties = staticmethod(get_device_properties)
    get_device_name = staticmethod(get_device_name)
    Stream = Stream
    Event = Event
    stream_guard = staticmethod(stream_guard)
    current_stream = staticmethod(current_stream)


cuda = _CudaAlias()


# place classes + build-flag predicates re-exported for
# paddle.device.* parity (reference: python/paddle/device/__init__.py)
from ..core.place import (  # noqa: E402,F401
    IPUPlace,
    MLUPlace,
    XPUPlace,
    get_cudnn_version,
    is_compiled_with_cinn,
    is_compiled_with_mlu,
    is_compiled_with_rocm,
)
from ..distributed.env import ParallelEnv  # noqa: E402,F401
