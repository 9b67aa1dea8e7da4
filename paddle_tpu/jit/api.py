"""paddle_tpu.jit — to_static and the compiled TrainStep.

Reference: python/paddle/jit/api.py:197 (to_static). The reference needs a
bytecode JIT (SOT) + AST rewriting + a static IR + its own executor; on TPU
jax.jit IS that entire stack: to_static wraps a function/Layer so calls
trace once per input signature and run the cached XLA executable.

TrainStep is the performance path (SURVEY.md §7.2 stage 3): one jax.jit
containing forward + loss + backward (jax.grad) + optimizer update +
buffer updates, with donated argnums so parameter/optimizer-state memory is
reused in place on TPU.
"""
from __future__ import annotations

import functools
import inspect as _inspect
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec
import numpy as np

from ..core import random as random_mod
from ..core import tape as tape_mod
from ..core.dispatch import run_op, unwrap, wrap
from ..core.tensor import Tensor
from ..profiler.profiler import RecordEvent
from .functional import (functional_call, get_buffers, get_frozen,
                         get_params, write_back)


class InputSpec:
    """Shape/dtype spec (reference: paddle.static.InputSpec)."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = list(shape)
        self.dtype = dtype
        self.name = name

    def matches(self, shape, dtype) -> Optional[str]:
        """None if (shape, dtype) satisfies the spec, else the reason.
        None/-1 spec dims are wildcards (dynamic batch)."""
        shape = tuple(shape)
        if len(shape) != len(self.shape):
            return (f"rank mismatch: got {list(shape)}, spec expects "
                    f"{self.shape}")
        for got, want in zip(shape, self.shape):
            if want not in (None, -1) and got != want:
                return (f"shape mismatch: got {list(shape)}, spec expects "
                        f"{self.shape}")
        from ..core import dtype as dtype_mod
        try:
            want_np = dtype_mod.dtype(self.dtype).np_dtype
        except Exception:
            # a typo'd spec dtype must not silently disable the check
            return (f"spec dtype {self.dtype!r} is not a known dtype "
                    "(typo in the InputSpec?)")
        if np.dtype(dtype) != np.dtype(want_np):
            return f"dtype mismatch: got {dtype}, spec expects {self.dtype}"
        return None

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"


def _sig_of(args, kwargs):
    parts = []
    for a in args:
        if isinstance(a, Tensor):
            parts.append(("T", tuple(a._data.shape), str(a._data.dtype)))
        elif isinstance(a, (jnp.ndarray, jax.Array, np.ndarray)):
            parts.append(("A", tuple(a.shape), str(a.dtype)))
        else:
            parts.append(("S", repr(a)))
    for k in sorted(kwargs):
        v = kwargs[k]
        if isinstance(v, Tensor):
            parts.append((k, tuple(v._data.shape), str(v._data.dtype)))
        elif isinstance(v, (jnp.ndarray, jax.Array, np.ndarray)):
            # shape/dtype only — repr(v) would bake element VALUES into
            # the cache key (a new entry per batch of data, and keys the
            # size of the array's print form)
            parts.append((k, tuple(v.shape), str(v.dtype)))
        else:
            parts.append((k, repr(v)))
    return tuple(parts)


class StaticFunction:
    """A function compiled per input signature; Tensor-in/Tensor-out and
    differentiable through the dygraph tape (the compiled forward is one
    tape op whose vjp is the compiled backward)."""

    def __init__(self, fn, input_spec=None, build_strategy=None,
                 backend=None, full_graph=True):
        self._fn = fn
        self._layer = None
        if hasattr(fn, "forward") and hasattr(fn, "named_parameters"):
            self._layer = fn
            self._fn = fn.forward
        self._input_spec = input_spec
        self._cache = {}
        # signatures that graph-broke -> eager calls since the pin; other
        # signatures keep their compiled entries. A pin is dropped (and
        # compilation retried) every _RETRY_AFTER fallback calls, so a
        # signature that traced badly once — e.g. before a warmup flag
        # flipped — is not condemned to eager forever. After
        # _MAX_RETRIES failed retries the pin becomes permanent — a
        # genuinely value-dependent branch must not pay a guaranteed-to-
        # fail re-trace every 16th call for the life of the process
        self._eager_sigs = {}
        self._retry_counts = {}
        self._child_sf = None  # lazily-built per-sublayer compilers
        self._warned_break = False
        functools.update_wrapper(self, self._fn)

    _RETRY_AFTER = 16
    _MAX_RETRIES = 3

    @property
    def layer(self):
        return self._layer

    @property
    def input_spec(self):
        return self._input_spec

    def concrete_program(self):
        return None  # no program world on TPU

    def _spec_list(self):
        if self._input_spec is None:
            return None
        return list(self._input_spec) \
            if isinstance(self._input_spec, (list, tuple)) \
            else [self._input_spec]

    def _validate_input_spec(self, tensor_args):
        """Honor the stored InputSpec: reject calls whose array shapes/
        dtypes contradict the declared signature (the reference's
        dy2static does this at Program build; here the check is the
        only thing standing between a typo and a silent recompile)."""
        specs = self._spec_list()
        if not specs:
            return
        for i, (spec, a) in enumerate(zip(specs, tensor_args)):
            if not isinstance(spec, InputSpec):
                continue
            arr = a._data if isinstance(a, Tensor) else a
            if not isinstance(arr, (jnp.ndarray, jax.Array, np.ndarray)):
                continue
            why = spec.matches(arr.shape, arr.dtype)
            if why is not None:
                name = getattr(self._fn, "__qualname__", "to_static fn")
                raise ValueError(
                    f"{name}: input #{i} violates input_spec: {why}")

    def inspect(self, *args, mesh=None, **kwargs):
        """Statically lint this function at the given example inputs —
        AST trace-safety pass plus jaxpr rule passes over an abstract
        trace (jax.make_jaxpr on ShapeDtypeStructs; nothing runs on
        device). With no arguments, shapes come from the stored
        InputSpec list. `mesh` (a Mesh, AbstractMesh, or {axis: degree}
        dict — still device-free) additionally runs the shard_lint
        SPMD/collective rules and attaches a static cost estimate.
        Returns an analysis.Report."""
        from ..analysis import lint_static_function
        return lint_static_function(self, args if args else None, kwargs,
                                    mesh=mesh)

    def _maybe_lint_first_compile(self, args, kwargs):
        """Opt-in (PADDLE_TPU_LINT=1) hook run when a signature first
        compiles: findings go through paddle_tpu.monitor counters and
        one warning. Never allowed to break the call."""
        from ..analysis import lint_on_first_compile
        lint_on_first_compile(self.inspect, *args, **kwargs)

    def _pure(self, static_kwargs):
        layer = self._layer
        fn = self._fn

        # array-valued kwargs ride along as one traced dict pytree,
        # re-wrapped and bound BY NAME — positional-tail binding would
        # attach them to the wrong parameter, and leaving them in
        # static_kwargs would bake their values into the closure while
        # the cache key only carries shape/dtype
        def wrap_kw(arr_kwargs):
            kw = dict(static_kwargs)
            for k, a in arr_kwargs.items():
                kw[k] = Tensor._from_array(a)
            return kw

        if layer is None:
            def pure(arr_kwargs, *arrays):
                with tape_mod.no_grad_guard():
                    targs = [Tensor._from_array(a) for a in arrays]
                    out = fn(*targs, **wrap_kw(arr_kwargs))
                return jax.tree_util.tree_map(
                    lambda t: t._data if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda t: isinstance(t, Tensor))
            return pure

        def pure(params, buffers, frozen, key, arr_kwargs, *arrays):
            out, new_buf = functional_call(
                layer, params, buffers, arrays, wrap_kw(arr_kwargs),
                frozen=frozen, rng_key=key)
            return out, new_buf
        return pure

    # tracer-concretization errors = the reference's "graph break":
    # value-dependent Python control flow the tracer cannot stage
    # (reference jit/sot/translate.py:91 falls back to eager for the
    # un-traceable region; here the region is the whole call)
    _BREAK_ERRORS = (
        jax.errors.TracerBoolConversionError,
        jax.errors.TracerIntegerConversionError,
        jax.errors.TracerArrayConversionError,
        jax.errors.ConcretizationTypeError,
    )

    def _graph_break(self, exc, args, kwargs):
        if not self._warned_break:
            import warnings
            name = getattr(self._fn, "__qualname__", repr(self._fn))
            how = ("keeping each traceable sublayer compiled and running "
                   "only the parent control flow eagerly"
                   if self._layer is not None else
                   "falling back to eager for this function")
            warnings.warn(
                f"to_static({name}): value-dependent Python control flow "
                f"cannot be traced ({type(exc).__name__}); {how}. Use "
                "paddle.static.nn.cond / while_loop to keep the whole "
                "graph compiled.", stacklevel=3)
            self._warned_break = True
        return self._fallback_call(args, kwargs)

    def _fallback_call(self, args, kwargs):
        """The reference's SOT breaks the graph at the un-traceable
        opcode and keeps the regions on both sides compiled
        (jit/sot/translate.py:91). The per-sublayer analog: run the
        parent's forward as Python, but route every sublayer call that
        originates from the eager region through its own StaticFunction
        — a 10-layer model with one value-dependent branch keeps the
        other layers compiled. Sublayer calls that happen *inside* an
        enclosing trace inline their original forward, so the largest
        traceable subtree compiles as one unit. Plain functions (no
        layer tree to segment) run fully eager."""
        if self._layer is None:
            return self._fn(*args, **kwargs)
        layer = self._layer
        # the compiled sublayer path returns fresh (tape-less) Tensors,
        # same as the whole-layer compiled path; when the caller is
        # recording gradients — through the params OR through a
        # grad-requiring input (frozen-model adversarial/inversion
        # loops) — the only correct fallback is full eager
        def _wants_grad(obj):
            leaves = jax.tree_util.tree_leaves(
                obj, is_leaf=lambda t: isinstance(t, Tensor))
            return any(isinstance(t, Tensor) and not t.stop_gradient
                       for t in leaves)

        if tape_mod.is_grad_enabled() and (
                any(not p.stop_gradient for p in layer.parameters())
                or _wants_grad((args, kwargs))):
            return layer(*args, **kwargs)
        if self._child_sf is None:
            self._child_sf = {}
        patched = []
        try:
            for name, child in layer.named_sublayers():
                if "forward" in child.__dict__:
                    continue  # already patched (shared module)
                sf = self._child_sf.get(name)
                if sf is None:
                    sf = StaticFunction(child)
                    self._child_sf[name] = sf
                child.forward = _child_compiled_forward(child, sf)
                patched.append(child)
            return layer(*args, **kwargs)
        finally:
            for child in patched:
                try:
                    del child.forward
                except AttributeError:
                    pass

    def _positionalize(self, tensor_args, kwargs):
        """Move keyword-passed arrays into their positional slots (by
        the function's signature) while the slots stay contiguous.
        Positional arrays get the full treatment — gradient flow, spec
        validation, _sig_of keying; only non-contiguous array kwargs
        are left to the (non-differentiable) traced-dict path."""
        if not kwargs:
            return kwargs
        try:
            params = list(_inspect.signature(
                self._fn).parameters.values())
        except (TypeError, ValueError):
            return kwargs
        kwargs = dict(kwargs)
        for p in params[len(tensor_args):]:
            if (p.kind != p.POSITIONAL_OR_KEYWORD
                    or p.name not in kwargs
                    or not isinstance(kwargs[p.name],
                                      (Tensor, jnp.ndarray, jax.Array,
                                       np.ndarray))):
                break
            tensor_args.append(kwargs.pop(p.name))
        return kwargs

    def __call__(self, *args, **kwargs):
        tensor_args = list(args)
        kwargs = self._positionalize(tensor_args, kwargs)
        # the positionalized form IS the call from here on — the
        # graph-break fallback and the lint hook must see the same
        # program the trace saw, not the original kwargs (a moved
        # kwarg would silently fall back to its default)
        args = tuple(tensor_args)
        tensor_kwargs = {}
        static_kwargs = {}
        for k, v in kwargs.items():
            if isinstance(v, Tensor) and not v.stop_gradient \
                    and tape_mod.is_grad_enabled():
                import warnings
                warnings.warn(
                    f"to_static: tensor kwarg '{k}' requires grad but "
                    "cannot take a positional slot (keyword-only, or "
                    "behind a non-tensor kwarg); gradients do NOT flow "
                    "through keyword tensors in the compiled path — "
                    "pass it positionally.", stacklevel=2)
            if isinstance(v, (Tensor, jnp.ndarray, jax.Array, np.ndarray)):
                # traced by name through _pure's arr_kwargs dict: in
                # static_kwargs the VALUES would be baked into the
                # jitted closure while the cache key only carries
                # shape/dtype (stale replay); on the positional tail
                # they would bind to the wrong parameter. Gradients do
                # NOT flow through this dict — only through positional
                # (incl. positionalized) tensors
                tensor_kwargs[k] = v
            else:
                static_kwargs[k] = v
        self._validate_input_spec(tensor_args)
        sig = _sig_of(tensor_args, {**static_kwargs, **tensor_kwargs})
        kw_arrays = {k: unwrap(v) for k, v in tensor_kwargs.items()}
        pinned = self._eager_sigs.get(sig)
        if pinned is not None:
            if (pinned + 1 < self._RETRY_AFTER
                    or self._retry_counts.get(sig, 0)
                    >= self._MAX_RETRIES):
                if pinned + 1 < self._RETRY_AFTER:
                    self._eager_sigs[sig] = pinned + 1
                return self._fallback_call(args, kwargs)
            # the branch value (or a warmup flag) may have changed since
            # the pin: drop it and give the full graph another chance
            del self._eager_sigs[sig]
            self._retry_counts[sig] = self._retry_counts.get(sig, 0) + 1
        entry = self._cache.get(sig)
        if self._layer is None:
            if entry is None:
                entry = jax.jit(self._pure(static_kwargs))
                self._cache[sig] = entry
                self._maybe_lint_first_compile(args, kwargs)
            try:
                # ONE tape op: compiled forward, vjp = compiled backward
                # (kwarg arrays ride in the leading dict — non-diff)
                return run_op("jit_fn", entry, [kw_arrays] + tensor_args)
            except self._BREAK_ERRORS as exc:
                self._eager_sigs[sig] = 0
                return self._graph_break(exc, args, kwargs)

        layer = self._layer
        params = get_params(layer)
        buffers = get_buffers(layer)
        frozen = get_frozen(layer)
        if entry is None:
            entry = jax.jit(self._pure(static_kwargs))
            self._cache[sig] = entry
            self._maybe_lint_first_compile(args, kwargs)
        key = random_mod.next_key()
        arrays = [unwrap(a) for a in tensor_args]
        try:
            out_arrays, new_buf = entry(params, buffers, frozen, key,
                                        kw_arrays, *arrays)
        except self._BREAK_ERRORS as exc:
            self._eager_sigs[sig] = 0
            return self._graph_break(exc, args, kwargs)
        write_back(layer, {}, new_buf)
        return jax.tree_util.tree_map(
            lambda a: wrap(a), out_arrays,
            is_leaf=lambda a: isinstance(a, (jax.Array, np.ndarray)))


def _under_trace(args, kwargs):
    leaves = jax.tree_util.tree_leaves(
        (args, kwargs),
        is_leaf=lambda t: isinstance(t, Tensor))
    for leaf in leaves:
        arr = leaf._data if isinstance(leaf, Tensor) else leaf
        if isinstance(arr, jax.core.Tracer):
            return True
    return False


def _child_compiled_forward(child, sf):
    """Instance-level forward override used during a parent's partial
    (graph-broken) call: the sublayer call goes through its own
    StaticFunction. The override is lifted around the delegated call so
    tracing (and any eager fallback inside ``sf``) reaches the real
    forward instead of recursing into this wrapper. Calls arriving with
    tracer inputs are already inside an enclosing sublayer's trace —
    inline the original forward there (a nested StaticFunction would
    write traced buffers back into live layers)."""
    def wrapper(*a, **kw):
        del child.forward
        try:
            if _under_trace(a, kw):
                return child.forward(*a, **kw)
            return sf(*a, **kw)
        finally:
            child.forward = wrapper
    return wrapper


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Decorator/wrapper compiling a function or Layer's forward."""
    def wrap_fn(fn):
        return StaticFunction(fn, input_spec, build_strategy, backend)
    if function is None:
        return wrap_fn
    return wrap_fn(function)


def not_to_static(fn=None):
    if fn is None:
        return lambda f: f
    return fn


def enable_to_static(flag=True):
    pass


def _committed_sharding(a):
    """The NamedSharding an array is committed to, else None."""
    sh = getattr(a, "sharding", None)
    if isinstance(sh, NamedSharding) and getattr(a, "committed", False):
        return sh
    return None


class TrainStep:
    """Whole-train-step compilation:

        loss = step(inputs, labels)

    runs forward + loss + jax.grad + optimizer update + buffer update as a
    single donated jax.jit executable and syncs results back into the
    Layer/Optimizer objects so eager code (hooks, prints, checkpoints)
    sees fresh state.
    """

    def __init__(self, model, loss_fn, optimizer, amp_dtype=None,
                 donate=True):
        self._model = model
        self._loss_fn = loss_fn
        self._opt = optimizer
        self._amp_dtype = amp_dtype
        self._params = get_params(model)
        self._frozen = get_frozen(model)
        self._buffers = get_buffers(model)
        self._opt_state = optimizer.init_state_pytree(self._params)
        self._compiled = {}
        self._donate = donate
        from .functional import _tensor_registry
        self._registry = _tensor_registry(model)
        self._state_shardings = None
        mesh = self._state_mesh()
        if mesh is not None:
            self._commit_state(mesh)

    def _state_mesh(self):
        """The mesh a state leaf is committed to (a model built from
        mpu layers commits its weights at construction), or None: a
        plain model, which a single device runs with nothing to pin."""
        for a in jax.tree_util.tree_leaves(
                (self._params, self._frozen, self._buffers)):
            sh = _committed_sharding(a)
            if sh is not None:
                return sh.mesh
        return None

    def _commit_state(self, mesh):
        """Commit EVERY state leaf to an explicit sharding on `mesh`,
        and remember them: the step's outputs are held to the same
        shardings (_make_step), so the state keeps its layout from
        step to step — one executable instead of one per change of
        commitment, donation that can alias, and a parameter that never
        loses its 'mp' split to the compiler's own choice of output
        layout. Leaves already committed to `mesh` keep their sharding,
        the rest replicate; an optimizer slot shaped like its parameter
        follows it (_slot_sharding), scalar slots replicate."""
        rep = NamedSharding(mesh, PartitionSpec())

        def own(a):
            sh = _committed_sharding(a)
            return sh if sh is not None and sh.mesh == mesh else rep

        params_sh = {n: own(a) for n, a in self._params.items()}
        opt_sh = {
            n: {slot: self._slot_sharding(params_sh[n], a.shape)
                if a.shape and a.shape == self._params[n].shape else rep
                for slot, a in st.items()}
            for n, st in self._opt_state.items()}
        self._params = jax.device_put(self._params, params_sh)
        self._opt_state = jax.device_put(self._opt_state, opt_sh)
        self._buffers = jax.device_put(self._buffers, rep)
        self._frozen = jax.device_put(self._frozen, rep)
        self._state_shardings = (params_sh, rep, opt_sh, rep)

    def _slot_sharding(self, param_sharding, shape):
        return param_sharding

    def _build_step(self):
        """The raw python step function (un-jitted) — also traced
        abstractly by analysis.lint_train_step."""
        model, loss_fn, opt = self._model, self._loss_fn, self._opt
        amp_dtype = self._amp_dtype

        def loss_of(params, buffers, frozen, key, inputs, labels):
            if amp_dtype is not None:
                cast_params = jax.tree_util.tree_map(
                    lambda a: a.astype(amp_dtype)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
                # O2 semantics: float inputs run in the compute dtype too
                # (lax.conv rejects mixed fp32-input/bf16-weight; labels
                # stay untouched for the loss)
                inputs = jax.tree_util.tree_map(
                    lambda a: a.astype(amp_dtype)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a, inputs)
            else:
                cast_params = params
            out, new_buf = functional_call(
                model, cast_params, buffers, inputs, {},
                frozen=frozen, rng_key=key, training=True)
            with tape_mod.no_grad_guard():
                out_t = jax.tree_util.tree_map(
                    lambda a: Tensor._from_array(a), out,
                    is_leaf=lambda a: isinstance(a, jax.Array))
                lab_t = jax.tree_util.tree_map(
                    lambda a: Tensor._from_array(a), labels,
                    is_leaf=lambda a: isinstance(a, jax.Array))
                loss = loss_fn(out_t, lab_t)
            return unwrap(loss).astype(jnp.float32), new_buf

        def step(params, buffers, frozen, opt_state, key, lr, inputs,
                 labels):
            (loss, new_buf), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params, buffers, frozen, key, inputs,
                                       labels)
            if opt._grad_clip is not None:
                grads = _clip_pytree(grads, opt._grad_clip)
            new_params, new_opt_state = opt.apply_gradients_pytree(
                params, grads, opt_state, lr)
            return new_params, new_buf, new_opt_state, loss

        return step

    def _make_step(self):
        donate = (0, 1, 3) if self._donate else ()
        # (params, buffers, opt_state, loss) are held to the shardings
        # _commit_state chose; None (a plain single-device model) leaves
        # them to the compiler
        return jax.jit(self._build_step(), donate_argnums=donate,
                       out_shardings=self._state_shardings)

    @staticmethod
    def _leaf_sig(tree):
        return tuple(
            (tuple(a.shape), str(a.dtype))
            if isinstance(a, (jnp.ndarray, jax.Array, np.ndarray))
            else ("S", repr(a))
            for a in jax.tree_util.tree_leaves(tree))

    def inspect(self, inputs, labels, mesh=None):
        """Statically lint the fused train step at the given example
        inputs/labels (Tensors, arrays, or InputSpecs — only shapes and
        dtypes are read; nothing executes on device). `mesh` adds the
        shard_lint collective rules + cost model over the same trace.
        Returns an analysis.Report."""
        from ..analysis import lint_train_step
        return lint_train_step(self, inputs, labels, mesh=mesh)

    def lower(self, inputs, labels):
        """The fused step lowered — not compiled, not run — at these
        inputs/labels and the step's live state (shapes, dtypes and
        shardings are read; no buffer is touched or donated).
        ``.as_text()`` shows what the program baked in (a Pallas kernel
        is a ``tpu_custom_call``); ``.compile()`` is the ahead-of-time
        route."""
        from ..analysis.functional_shapes import rng_key_struct

        def struct(a):
            a = unwrap(a) if isinstance(a, Tensor) else a
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=getattr(a, "sharding", None))

        if not isinstance(inputs, (list, tuple)):
            inputs = (inputs,)
        state, data = jax.tree_util.tree_map(
            struct, ((self._params, self._buffers, self._frozen,
                      self._opt_state), (tuple(inputs), labels)),
            is_leaf=lambda t: isinstance(t, Tensor))
        params, buffers, frozen, opt_state = state
        return self._make_step().lower(
            params, buffers, frozen, opt_state, rng_key_struct(),
            jax.ShapeDtypeStruct((), jnp.float32), *data)

    def __call__(self, inputs, labels):
        # three host phases, named on the profiler's clock (inert when
        # nothing records): what the host does around the one program
        with RecordEvent("trainstep.prepare"):
            if not isinstance(inputs, (list, tuple)):
                inputs = (inputs,)
            in_arrays = tuple(unwrap(x) for x in inputs)
            lab_arrays = jax.tree_util.tree_map(
                lambda t: unwrap(t), labels,
                is_leaf=lambda t: isinstance(t, Tensor))
            # label leaves are part of the executable's signature too: a
            # label shape/dtype change must not silently reuse (and
            # retrace under) the executable cached for the old labels
            sig = (self._leaf_sig(in_arrays), self._leaf_sig(lab_arrays))
            fn = self._compiled.get(sig)
            if fn is None:
                fn = self._make_step()
                self._compiled[sig] = fn
                from ..analysis import lint_on_first_compile
                lint_on_first_compile(self.inspect, inputs, labels)
            key = random_mod.next_key()
            lr = jnp.asarray(self._opt.get_lr(), jnp.float32)
        with RecordEvent("trainstep.dispatch"):
            self._params, self._buffers, self._opt_state, loss = fn(
                self._params, self._buffers, self._frozen,
                self._opt_state, key, lr, in_arrays, lab_arrays)
        # re-point the Layer's tensors at the fresh outputs (reference
        # swap, no copies) — the donated inputs they held are now deleted,
        # and any eager read (state_dict/checkpoint/print) must see live
        # arrays without an explicit sync_to_model call
        with RecordEvent("trainstep.write_back"):
            write_back(self._model, self._params, self._buffers,
                       registry=self._registry)
        from ..distributed import watchdog
        watchdog.maybe_start_and_tick()
        return wrap(loss)

    def sync_to_model(self):
        """Write compiled-side state back into Layer/Optimizer tensors."""
        write_back(self._model, self._params, self._buffers)
        name_of = {name: p for name, p in self._model.named_parameters()}
        for name, state in self._opt_state.items():
            p = name_of.get(name)
            if p is not None:
                self._opt._accumulators[id(p)] = dict(state)

    def sync_from_model(self):
        self._params = get_params(self._model)
        self._frozen = get_frozen(self._model)
        self._buffers = get_buffers(self._model)

    @property
    def loss_scale(self):
        return 1.0


def _clip_pytree(grads, clip):
    """Apply a nn.Clip* object to a {name: array} pytree inside jit."""
    from ..nn.clip import (ClipGradByGlobalNorm, ClipGradByNorm,
                           ClipGradByValue)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if isinstance(clip, ClipGradByValue):
        leaves = [jnp.clip(g, clip.min, clip.max) for g in leaves]
    elif isinstance(clip, ClipGradByNorm):
        out = []
        for g in leaves:
            n = jnp.sqrt(jnp.sum(jnp.square(g)))
            s = jnp.where(n > clip.clip_norm,
                          clip.clip_norm / jnp.maximum(n, 1e-12), 1.0)
            out.append(g * s)
        leaves = out
    elif isinstance(clip, ClipGradByGlobalNorm):
        total = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in leaves)
        gn = jnp.sqrt(total)
        scale = clip.clip_norm / jnp.maximum(gn, clip.clip_norm)
        leaves = [(g.astype(jnp.float32) * scale).astype(g.dtype)
                  for g in leaves]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def compile_train_step(model, loss_fn, optimizer, **kw):
    return TrainStep(model, loss_fn, optimizer, **kw)
