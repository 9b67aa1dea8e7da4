"""Device API (reference: python/paddle/device/__init__.py:281 set_device).

TPU is the accelerator; `paddle.device.cuda.*` compat shims map to it so
reference-shaped scripts run unchanged.
"""
from __future__ import annotations

import jax

from ..core.place import (  # noqa: F401
    CPUPlace, CUDAPlace, Place, TPUPlace, XPUPlace, get_device,
    is_compiled_with_cuda, is_compiled_with_tpu, is_compiled_with_xpu,
    set_device,
)


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu",)]


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [d for d in get_available_device() if not d.startswith("cpu")]


def device_count():
    return len(jax.devices()) if is_compiled_with_tpu() else 1


class Stream:
    """Compat shim: XLA streams are managed by the runtime; operations on a
    Stream are ordering no-ops (execution is already well-ordered per device)."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        _sync()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()

    def query(self):
        return True


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        _sync()

    def elapsed_time(self, end_event):
        return 0.0


def _sync():
    (jax.device_put(0) + 0).block_until_ready()


def synchronize(device=None):
    _sync()


def current_stream(device=None):
    return Stream(device)


def set_stream(stream):
    return stream


class _CudaNamespace:
    Stream = Stream
    Event = Event

    @staticmethod
    def synchronize(device=None):
        _sync()

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def is_available():
        return is_compiled_with_tpu()

    @staticmethod
    def current_stream(device=None):
        return Stream(device)

    @staticmethod
    def max_memory_allocated(device=None):
        from .monitor import max_memory_allocated as f
        return f(device)

    @staticmethod
    def max_memory_reserved(device=None):
        from .monitor import max_memory_reserved as f
        return f(device)

    @staticmethod
    def memory_allocated(device=None):
        from .monitor import memory_allocated as f
        return f(device)

    @staticmethod
    def memory_reserved(device=None):
        from .monitor import memory_reserved as f
        return f(device)

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def get_device_properties(device=None):
        d = jax.devices()[0]
        class Props:
            name = getattr(d, "device_kind", "TPU")
            total_memory = _memory_stat("bytes_limit") or (16 << 30)
            major, minor = 0, 0
            multi_processor_count = 1
        return Props()

    @staticmethod
    def get_device_name(device=None):
        return getattr(jax.devices()[0], "device_kind", "TPU")

    @staticmethod
    def get_device_capability(device=None):
        return (0, 0)


def _memory_stat(key):
    from .monitor import _device_stats
    return int(_device_stats(0).get(key, 0))


cuda = _CudaNamespace()
xpu = cuda
from . import monitor  # noqa: F401
from .monitor import (max_memory_allocated, max_memory_reserved,  # noqa: F401
                      memory_allocated, memory_reserved)


def get_cudnn_version():
    """CUDA compat (reference: device.get_cudnn_version): no cuDNN in the
    XLA:TPU stack — None, like a CPU-only reference build."""
    return None


def is_compiled_with_rocm():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    """The graph compiler here is XLA, not CINN."""
    return False


def is_compiled_with_custom_device(device_name=None):
    """PJRT plugins are the custom-device mechanism."""
    import jax
    try:
        platforms = {d.platform for d in jax.devices()}
    except Exception:
        return False
    return device_name in platforms if device_name else bool(platforms)


def is_compiled_with_distribute():
    """Distributed is always built in (jax.distributed + mesh)."""
    return True


class IPUPlace:
    def __init__(self, *a):
        raise RuntimeError("IPU is not a PJRT backend in this build")


import contextlib as _contextlib


@_contextlib.contextmanager
def stream_guard(stream=None):
    """Streams are XLA-managed; kept as a no-op scope (reference:
    device.stream_guard)."""
    yield
