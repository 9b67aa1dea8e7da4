"""nn.Layer — the module base class.

Reference: python/paddle/nn/layer/layers.py:354 (class Layer): parameter /
sublayer / buffer registries via __setattr__, structured state_dict naming,
train/eval propagation, forward hooks, apply/to. TPU-native addition: a
Layer is *functionalizable* — ``paddle_tpu.jit`` lifts the parameter and
buffer registries into a jax pytree and re-binds them to traced values while
tracing ``forward``, which is how whole train steps compile under jax.jit
without a separate static-graph world.
"""
from __future__ import annotations

import collections
import contextlib
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core import dtype as dtype_mod
from ...core.tensor import Tensor
from ...framework.param_attr import Parameter, ParamAttr
from .. import initializer as init_mod


class HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


# dtype that create_parameter gives a parameter no layer named one for,
# while a `param_dtype` block is open (None: the layer's own default),
# and whether it waits for each parameter's initialiser
_construct_dtype = None
_construct_wait = False


@contextlib.contextmanager
def param_dtype(dtype, wait=False):
    """Build the layers constructed inside in `dtype` from the start: a
    model too large to exist in float32 first (``net.astype`` needs the
    float32 copy to fit beside the result) is built under this. With
    `wait` each parameter is waited for before the next is drawn: an
    initialiser draws in float32 and rounds, and the draws of the
    parameters still in flight lie beside the weights (5.8 GB above a
    7 GB model at the peak, by the timing of the run); the host then
    no longer runs ahead of the device."""
    global _construct_dtype, _construct_wait
    before = _construct_dtype, _construct_wait
    _construct_dtype, _construct_wait = dtype, wait
    try:
        yield
    finally:
        _construct_dtype, _construct_wait = before


class Layer:
    """Base class for all neural network layers."""

    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = dtype
        self._name_scope = name_scope or self.__class__.__name__.lower()
        self._parameters = collections.OrderedDict()
        self._sub_layers = collections.OrderedDict()
        self._buffers = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._hook_id = 0
        self._casted_by_pure_fp16 = False

    # -- attribute magic -----------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError(
                    "super().__init__() must run before assigning Parameters")
            for reg in (layers, buffers):
                if reg is not None:
                    reg.pop(name, None)
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError(
                    "super().__init__() must run before assigning sublayers")
            for reg in (params, buffers):
                if reg is not None:
                    reg.pop(name, None)
            layers[name] = value
            self.__dict__.pop(name, None)
        elif buffers is not None and name in buffers:
            if value is None or isinstance(value, Tensor):
                buffers[name] = value
            else:
                raise TypeError(
                    f"buffer {name} can only be reassigned a Tensor/None")
        else:
            if params is not None and name in params:
                if value is None:
                    params.pop(name)
                    object.__setattr__(self, name, None)
                    return
                raise TypeError(
                    f"{name} is a registered Parameter; assign a Parameter "
                    "or use add_parameter")
            if layers is not None and name in layers:
                if value is None:
                    layers.pop(name)
                    object.__setattr__(self, name, None)
                    return
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        # only called when normal lookup fails
        for reg_name in ("_parameters", "_sub_layers", "_buffers"):
            reg = self.__dict__.get(reg_name)
            if reg is not None and name in reg:
                return reg[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for reg_name in ("_parameters", "_sub_layers", "_buffers"):
            reg = self.__dict__.get(reg_name)
            if reg is not None and name in reg:
                del reg[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        extra = list(self._parameters) + list(self._sub_layers) + \
            list(self._buffers)
        return sorted(set(super().__dir__() + extra))

    # -- parameter creation --------------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        """Reference: layers.py Layer.create_parameter."""
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        dtype = dtype or _construct_dtype or self._dtype or "float32"
        initializer = attr.initializer or default_initializer
        if initializer is None:
            glob = (init_mod.global_bias_initializer() if is_bias
                    else init_mod.global_weight_initializer())
            if glob is not None:
                initializer = glob
            elif is_bias:
                initializer = init_mod.Constant(0.0)
            else:
                initializer = init_mod.XavierNormal()
        np_dt = dtype_mod.dtype(dtype).np_dtype
        p = Parameter(np.zeros([int(s) for s in shape], np_dt),
                      trainable=attr.trainable, name=attr.name,
                      regularizer=attr.regularizer, need_clip=attr.need_clip,
                      learning_rate=attr.learning_rate)
        from ..initializer import lazy_init
        if lazy_init.in_lazy_mode():
            p._lazy_initializer = initializer
        else:
            initializer(p)
            if _construct_wait:
                jax.block_until_ready(p._data)
        return p

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter or None")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        if not isinstance(sublayer, Layer):
            raise TypeError("add_sublayer expects a Layer")
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            raise TypeError("register_buffer expects a Tensor or None")
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        self.__dict__.pop(name, None)
        return tensor

    # -- traversal -----------------------------------------------------------
    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self):
        seen = set()
        for name, l in self._sub_layers.items():
            if l is not None and id(l) not in seen:
                seen.add(id(l))
                yield name, l

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, l in self.named_children():
            sub_prefix = prefix + ("." if prefix else "") + name
            yield from l.named_sublayers(prefix=sub_prefix, include_self=True,
                                         layers_set=layers_set)

    def parameters(self, include_sublayers=True):
        return [p for _, p in
                self.named_parameters(include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        gen = self.named_sublayers(prefix=prefix, include_self=True) \
            if include_sublayers else [(prefix, self)]
        for lp, layer in gen:
            for name, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (lp + ("." if lp else "") + name, p)

    def buffers(self, include_sublayers=True):
        return [b for _, b in
                self.named_buffers(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        gen = self.named_sublayers(prefix=prefix, include_self=True) \
            if include_sublayers else [(prefix, self)]
        for lp, layer in gen:
            for name, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (lp + ("." if lp else "") + name, b)

    def apply(self, fn):
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    # -- mode ----------------------------------------------------------------
    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    # -- hooks ---------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- execution -----------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__} must implement forward()")

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            out = hook(self, inputs, outputs)
            if out is not None:
                outputs = out
        return outputs

    # -- state dict ----------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else \
            collections.OrderedDict()
        for name, p in self.named_parameters(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            dest[name] = p
        for name, b in self.named_buffers(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            short = name.rsplit(".", 1)[-1]
            owner = self
            if "." in name:
                for part in name.split(".")[:-1]:
                    owner = getattr(owner, part)
            if short not in owner._non_persistable_buffer_names:
                dest[name] = b
        return dest

    to_static_state_dict = state_dict

    def set_state_dict(self, state_dict, use_structured_name=True):
        """Load arrays into existing parameters/buffers by structured name.
        Returns (missing_keys, unexpected_keys) like the reference."""
        own = self.state_dict()
        missing, matched = [], set()
        for name, target in own.items():
            if name not in state_dict:
                missing.append(name)
                continue
            src = state_dict[name]
            arr = src._data if isinstance(src, Tensor) else jnp.asarray(src)
            if tuple(arr.shape) != tuple(target._data.shape):
                raise ValueError(
                    f"shape mismatch for {name}: loading {arr.shape} into "
                    f"{tuple(target._data.shape)}")
            target._data = arr.astype(target._data.dtype)
            matched.add(name)
        unexpected = [k for k in state_dict if k not in own]
        return missing, unexpected

    load_dict = set_state_dict

    # -- dtype / device movement --------------------------------------------
    def _transform(self, fn):
        for _, p in self.named_parameters():
            p._data = fn(p._data)
        for _, b in self.named_buffers():
            b._data = fn(b._data)
        return self

    def to(self, device=None, dtype=None, blocking=None):
        import jax
        from ...core import place as place_mod

        def fn(a):
            if dtype is not None:
                want = dtype_mod.dtype(dtype).np_dtype
                if jnp.issubdtype(a.dtype, jnp.floating):
                    a = a.astype(want)
            if device is not None:
                place = device
                if isinstance(place, str):
                    place = place_mod.CPUPlace() if place.startswith("cpu") \
                        else place_mod.TPUPlace(
                            int(place.split(":")[1]) if ":" in place else 0)
                a = jax.device_put(a, place.jax_device())
            return a
        return self._transform(fn)

    def astype(self, dtype):
        want = dtype_mod.dtype(dtype).np_dtype
        return self._transform(
            lambda a: a.astype(want)
            if jnp.issubdtype(a.dtype, jnp.floating) else a)

    def float(self):
        return self.astype("float32")

    def half(self):
        return self.astype("float16")

    def bfloat16(self):
        return self.astype("bfloat16")

    # -- misc ----------------------------------------------------------------
    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    clear_grad = clear_gradients

    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, l in self.named_children():
            sub = repr(l).split("\n")
            sub = [sub[0]] + ["  " + s for s in sub[1:]]
            lines.append(f"  ({name}): " + "\n".join(sub))
        main = f"{type(self).__name__}({extra}" + \
            (")" if not lines else "\n" + "\n".join(lines) + "\n)")
        return main

    def __len__(self):
        return len(self._sub_layers)
