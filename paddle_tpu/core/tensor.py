"""paddle_tpu.Tensor — the user-facing tensor.

Rebuild of the reference's eager Tensor (pybind TensorObject,
/root/reference/paddle/fluid/pybind/eager.cc:71, with python methods patched in
python/paddle/base/dygraph/tensor_patch_methods.py). Here a Tensor wraps a
``jax.Array`` plus autograd meta; data lives wherever XLA put it (TPU HBM by
default). Ops execute eagerly through jnp (each lowered+cached by XLA) and are
recorded on the tape (core/tape.py) for dygraph backward.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtype_mod
from . import place as place_mod
from . import tape as tape_mod
from .dtype import DType


def _coerce_array(data, dt: Optional[DType], place=None):
    """Convert python data to a jax array with paddle default-dtype rules
    (python floats -> default float dtype, python ints -> int64)."""
    if isinstance(data, Tensor):
        arr = data._data
    elif isinstance(data, jax.Array):
        arr = data
    else:
        npd = np.asarray(data)
        if dt is None:
            if npd.dtype == np.float64:
                npd = npd.astype(dtype_mod.default_float_dtype().np_dtype)
            arr = jnp.asarray(npd)
        else:
            arr = jnp.asarray(npd)
    if dt is not None:
        want = dtype_mod.dtype(dt).np_dtype
        if arr.dtype != want:
            arr = arr.astype(want)
    if place is not None:
        arr = jax.device_put(arr, place.jax_device()
                             if isinstance(place, place_mod.Place) else place)
    return arr


class Tensor:
    """A multidimensional array on TPU/CPU with optional grad history."""

    __slots__ = ("_data", "stop_gradient", "grad", "name", "persistable",
                 "_meta", "is_leaf_", "__weakref__", "__dict__")

    def __init__(self, data=None, dtype=None, place=None, stop_gradient=True,
                 name=None, persistable=False):
        if data is None:
            data = []
        self._data = _coerce_array(data, dtype_mod.dtype(dtype)
                                   if dtype is not None else None, place)
        self.stop_gradient = bool(stop_gradient)
        self.grad: Optional[Tensor] = None
        self.name = name
        self.persistable = persistable
        self._meta: Optional[tape_mod.AutogradMeta] = None
        self.is_leaf_ = True

    # -- construction helpers ------------------------------------------------
    @classmethod
    def _from_array(cls, arr, stop_gradient=True, name=None):
        t = cls.__new__(cls)
        t._data = arr
        t.stop_gradient = stop_gradient
        t.grad = None
        t.name = name
        t.persistable = False
        t._meta = None
        t.is_leaf_ = True
        return t

    # -- basic properties ----------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    ndimension = ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def dtype(self) -> DType:
        return dtype_mod.dtype(self._data.dtype)

    @property
    def place(self):
        try:
            dev = next(iter(self._data.devices()))
        except Exception:
            return place_mod.CPUPlace()
        if dev.platform == "tpu":
            return place_mod.TPUPlace(dev.id)
        return place_mod.CPUPlace()

    @property
    def is_leaf(self):
        return self._meta is None or self._meta.node is None

    @property
    def T(self):
        from .. import ops
        return ops.manipulation.t(self)

    @property
    def mT(self):
        from .. import ops
        perm = list(range(self.ndim))
        perm[-1], perm[-2] = perm[-2], perm[-1]
        return ops.manipulation.transpose(self, perm)

    def dim(self):
        return self.ndim

    def numel(self):
        return self.size

    def element_size(self):
        return self.dtype.itemsize

    # -- data access ---------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self._data)

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def __dlpack__(self, *a, **k):
        return self._data.__dlpack__(*a, **k)

    # -- autograd ------------------------------------------------------------
    def _ensure_meta(self) -> tape_mod.AutogradMeta:
        if self._meta is None:
            self._meta = tape_mod.AutogradMeta()
        return self._meta

    def backward(self, grad_tensor=None, retain_graph=False):
        tape_mod.backward([self], [grad_tensor], retain_graph=retain_graph)

    def register_hook(self, hook):
        meta = self._ensure_meta()
        meta.hooks.append(hook)

        class _Handle:
            def remove(_self):
                if hook in meta.hooks:
                    meta.hooks.remove(hook)
        return _Handle()

    def clear_grad(self, set_to_zero=False):
        if set_to_zero and self.grad is not None:
            self.grad._data = jnp.zeros_like(self.grad._data)
        else:
            self.grad = None

    clear_gradient = clear_grad

    def detach(self) -> "Tensor":
        t = Tensor._from_array(self._data, stop_gradient=True, name=self.name)
        return t

    def detach_(self) -> "Tensor":
        self._meta = None
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from .. import ops
        return ops.math.clone(self)

    @property
    def requires_grad(self):
        return not self.stop_gradient

    @requires_grad.setter
    def requires_grad(self, v):
        self.stop_gradient = not v

    # -- device / dtype movement --------------------------------------------
    def to(self, *args, **kwargs):
        t = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, (DType,)) or (isinstance(a, str) and _is_dtype_str(a)):
                t = t.astype(a)
            elif isinstance(a, place_mod.Place):
                t = Tensor._from_array(jax.device_put(t._data, a.jax_device()),
                                       t.stop_gradient, t.name)
            elif isinstance(a, str):
                # device strings: "cpu", "tpu:0"; "gpu:N"/"cuda:N" map to the
                # TPU chip for reference-script compatibility (_parse_place).
                p = _parse_place(a)
                t = Tensor._from_array(jax.device_put(t._data, p.jax_device()),
                                       t.stop_gradient, t.name)
        return t

    def cpu(self):
        return Tensor._from_array(
            jax.device_put(self._data, jax.local_devices(backend="cpu")[0]),
            self.stop_gradient, self.name)

    def tpu(self, device_id=0):
        return Tensor._from_array(
            jax.device_put(self._data,
                           place_mod.TPUPlace(device_id).jax_device()),
            self.stop_gradient, self.name)

    cuda = tpu  # reference-API alias: the accelerator here is TPU

    def pin_memory(self):
        return self.cpu()

    def astype(self, dt):
        from .. import ops
        return ops.manipulation.cast(self, dt)

    def cast(self, dt):
        return self.astype(dt)

    # -- value setters -------------------------------------------------------
    def set_value(self, value):
        arr = _coerce_array(value, self.dtype)
        if tuple(arr.shape) != tuple(self._data.shape):
            raise ValueError(
                f"set_value shape mismatch: {arr.shape} vs {self._data.shape}")
        self._data = arr
        return self

    def copy_(self, other, blocking=True):
        return self.set_value(other)

    def fill_(self, value):
        self._data = jnp.full_like(self._data, value)
        return self

    def zero_(self):
        self._data = jnp.zeros_like(self._data)
        return self

    # -- repr ----------------------------------------------------------------
    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                f"place={self.place}{grad_info},\n       {self.numpy()!r})")

    __str__ = __repr__

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-D tensor")
        return self._data.shape[0]

    def __bool__(self):
        return bool(self.numpy())

    def __int__(self):
        return int(self.numpy())

    def __float__(self):
        return float(self.numpy())

    def __index__(self):
        return int(self.numpy())

    def __hash__(self):
        return id(self)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __format__(self, spec):
        if self.ndim == 0:
            return format(self.item(), spec)
        return str(self)


def _is_dtype_str(s: str) -> bool:
    try:
        dtype_mod.dtype(s)
        return True
    except Exception:
        return False


def _parse_place(s: str):
    if s.startswith("cpu"):
        return place_mod.CPUPlace()
    if ":" in s:
        kind, idx = s.split(":", 1)
        return place_mod.TPUPlace(int(idx))
    return place_mod.TPUPlace(0)


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    """paddle.to_tensor (reference: python/paddle/tensor/creation.py)."""
    if isinstance(data, Tensor):
        t = Tensor(data._data, dtype=dtype, place=place,
                   stop_gradient=stop_gradient)
        return t
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)
