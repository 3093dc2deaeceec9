"""Device management (reference: python/paddle/device/ — set_device, cuda
streams API).  TPU-native: devices come from jax; streams/events are no-ops
because XLA owns scheduling (reference needed explicit CUDA streams,
paddle/fluid/platform/device_context.h)."""
from __future__ import annotations

import jax

_current = [None]


def get_all_devices():
    return jax.devices()


def set_device(device: str):
    """Make ``device`` ('cpu', 'tpu', 'tpu:1', ...) the default placement.
    Raises when the process has no such device."""
    from ..core.tensor import _resolve_device
    jax.config.update("jax_default_device", _resolve_device(device))
    _current[0] = device
    return device


def get_device() -> str:
    if _current[0] is not None:
        return _current[0]
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []


def device_count():
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return jax.default_backend() == "tpu"


class Stream:
    """API-compat stub: XLA schedules asynchronously; explicit streams are not
    a TPU concept."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()

    def wait_event(self, event):
        pass


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


def synchronize(device=None):
    """Block until all queued work completes (paddle.device.synchronize)."""
    for d in jax.live_arrays():
        pass
    (jax.device_put(0) + 0).block_until_ready()


def current_stream(device=None):
    return Stream(device)


def stream_guard(stream):
    import contextlib
    return contextlib.nullcontext()


class cuda:
    """paddle.device.cuda compat namespace (maps to the accelerator)."""
    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        return jax.device_count()

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def max_memory_allocated(device=None):
        try:
            stats = jax.devices()[0].memory_stats()
            return stats.get("peak_bytes_in_use", 0)
        except Exception:
            return 0

    @staticmethod
    def memory_allocated(device=None):
        try:
            stats = jax.devices()[0].memory_stats()
            return stats.get("bytes_in_use", 0)
        except Exception:
            return 0

    @staticmethod
    def max_memory_reserved(device=None):
        return cuda.max_memory_allocated(device)

    @staticmethod
    def memory_reserved(device=None):
        return cuda.memory_allocated(device)

    @staticmethod
    def empty_cache():
        pass
