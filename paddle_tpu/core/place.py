"""Device/place abstraction.

Mirrors ``phi::Place`` (/root/reference/paddle/phi/common/place.h) but maps to
jax devices: TPUPlace(i) <-> jax.devices('tpu')[i], CPUPlace <-> host CPU.
"""
from __future__ import annotations

import functools

import jax


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        devs = [d for d in jax.devices() if d.platform == self.device_type]
        if not devs:
            raise RuntimeError(
                f"{self!r} was asked for explicitly, but JAX reports no "
                f"{self.device_type} device (default backend: "
                f"{jax.default_backend()})")
        return devs[min(self.device_id, len(devs) - 1)]


class CPUPlace(Place):
    device_type = "cpu"

    def jax_device(self):
        return jax.local_devices(backend="cpu")[0]


class TPUPlace(Place):
    device_type = "tpu"


class CustomPlace(Place):
    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


# Alias kept so reference-shaped code (`paddle.CUDAPlace(0)`) keeps working:
# on this framework the accelerator is the TPU.
CUDAPlace = TPUPlace
CUDAPinnedPlace = CPUPlace
XPUPlace = TPUPlace


@functools.lru_cache(maxsize=None)
def accelerator_available() -> bool:
    """Is the default JAX backend a TPU? The ONE platform predicate:
    kernel routing, device counts and placement all ask here."""
    return jax.default_backend() == "tpu"


_current_place = None


def get_device() -> str:
    p = _get_current_place()
    return f"{p.device_type}:{p.device_id}" if p.device_type != "cpu" else "cpu"


def _get_current_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = TPUPlace(0) if accelerator_available() else CPUPlace()
    return _current_place


def set_device(device) -> Place:
    """paddle.device.set_device('tpu'/'tpu:0'/'cpu'/'gpu:0')."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return device
    s = str(device)
    if ":" in s:
        kind, idx = s.split(":", 1)
        idx = int(idx)
    else:
        kind, idx = s, 0
    kind = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu"}.get(kind, kind)
    if kind == "cpu":
        _current_place = CPUPlace()
    elif kind == "tpu":
        _current_place = TPUPlace(idx)
    else:
        _current_place = CustomPlace(kind, idx)
    return _current_place


def default_jax_device():
    return _get_current_place().jax_device()


def is_compiled_with_cuda() -> bool:  # paddle compat
    return False


def is_compiled_with_tpu() -> bool:
    return accelerator_available()


def is_compiled_with_xpu() -> bool:
    return False
