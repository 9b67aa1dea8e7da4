"""DistributedTrainStep — the hybrid-parallel compiled train step.

Reference analog: the combination of fleet.distributed_model +
HybridParallelOptimizer.step + EagerReducer/sharding reducers
(SURVEY.md §3.3 steps 6-8). TPU-native: ONE jax.jit whose inputs carry
NamedShardings — batch sharded over the data axes, params over
'mp' (TP) / 'sharding' (ZeRO-3), optimizer state over 'sharding'
(ZeRO-1/2) — and GSPMD emits every collective the reference hand-codes
(grad allreduce, reduce-scatter, param allgather).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..jit.api import TrainStep
from . import mesh as mesh_mod


def _also_over(sharding, shape, axis: str):
    """`sharding` with `axis` added on the first free dim it divides
    (ZeRO-1/2: a moment follows its parameter AND splits over the
    sharding axis); unchanged when the axis is already used, has
    degree 1, or fits no dim."""
    deg = mesh_mod.axis_degree(axis)
    entries = list(sharding.spec) + [None] * (len(shape) - len(sharding.spec))
    used = {a for e in entries if e is not None
            for a in (e if isinstance(e, tuple) else (e,))}
    if deg <= 1 or axis in used:
        return sharding
    for d, size in enumerate(shape):
        if entries[d] is None and size % deg == 0:
            entries[d] = axis
            return NamedSharding(sharding.mesh, PartitionSpec(*entries))
    return sharding


def _batch_sharding(mesh, ndim):
    if ndim == 0:
        return None   # scalars (e.g. a dummy label) have no batch dim
    axes = [ax for ax in ("dp", "sharding")
            if mesh_mod.axis_degree(ax) > 1]
    if not axes:
        return None
    entry = tuple(axes) if len(axes) > 1 else axes[0]
    return NamedSharding(mesh, PartitionSpec(entry, *([None] * (ndim - 1))))


class DistributedTrainStep(TrainStep):
    """TrainStep whose state/batch placements implement DP + ZeRO + TP.

    sharding_stage: 0/None = pure DP; 1 = optimizer states sharded;
    2 = same compiled program as 1 (grad reduce-scatter falls out of
    GSPMD's partitioning of the update); 3 = params sharded too (set up
    by fleet.distributed_model via shard_parameters_fsdp).
    """

    def __init__(self, model, loss_fn, optimizer, amp_dtype=None,
                 donate=True, sharding_stage: Optional[int] = None):
        inner = getattr(optimizer, "_inner_opt", optimizer)
        self._mesh = mesh_mod.ensure_mesh()
        stage = sharding_stage
        if stage is None:
            stage = getattr(inner, "_sharding_stage", 0)
        self._sharding_stage = int(stage or 0)
        super().__init__(model, loss_fn, inner, amp_dtype=amp_dtype,
                         donate=donate)

    def _state_mesh(self):
        return self._mesh

    def _slot_sharding(self, param_sharding, shape):
        if self._sharding_stage >= 1:
            return _also_over(param_sharding, shape, "sharding")
        return param_sharding

    def _place(self, inputs, labels):
        """Commit the batch to its data-axis sharding."""
        if not isinstance(inputs, (list, tuple)):
            inputs = (inputs,)
        mesh = self._mesh

        def place(t):
            arr = getattr(t, "_data", t)
            arr = jnp.asarray(arr)
            sh = _batch_sharding(mesh, arr.ndim)
            if sh is not None and not isinstance(arr, jax.core.Tracer):
                arr = jax.device_put(arr, sh)
            from ..core.tensor import Tensor
            return Tensor._from_array(arr)

        inputs = tuple(place(x) for x in inputs)
        labels = jax.tree_util.tree_map(
            place, labels,
            is_leaf=lambda t: hasattr(t, "_data") or hasattr(t, "shape"))
        return inputs, labels

    def lower(self, inputs, labels):
        return super().lower(*self._place(inputs, labels))

    def __call__(self, inputs, labels):
        return super().__call__(*self._place(inputs, labels))
