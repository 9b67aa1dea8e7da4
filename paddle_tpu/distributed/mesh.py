"""Global device-mesh topology for hybrid parallelism.

Reference: python/paddle/distributed/fleet/base/topology.py:70
(CommunicateTopology — cartesian rank coordinates over the axis order
[pp, mp(=tp), sep, sharding, dp]) and fleet.py:674 (_init_hybrid_parallel_env,
which news a process group per axis).

TPU-native design: there are no process groups — ONE `jax.sharding.Mesh`
with named axes carries the whole topology, and every "group collective"
is a compiled XLA collective over one (or more) mesh axis names
(SURVEY.md §7.1). This module owns the global mesh: axis order is
outermost-first ('pp', 'dp', 'sharding', 'sep', 'mp') so that tensor
parallelism (highest-bandwidth traffic) lands on the innermost, fastest
ICI dimension, and pipeline stages (lowest traffic) on the outermost.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# Axis order, outermost first. 'mp' is tensor parallel (paddle naming);
# 'sharding' is the FSDP/ZeRO axis; 'sep' is the sequence/segment axis
# (also used for expert parallel via the same slot when configured).
HYBRID_AXES = ("pp", "dp", "sharding", "sep", "mp")

_global_mesh: Optional[Mesh] = None


def build_mesh(degrees: Dict[str, int], devices=None,
               axis_order: Sequence[str] = HYBRID_AXES,
               dcn_degrees: Optional[Dict[str, int]] = None) -> Mesh:
    """Build a Mesh from per-axis degrees (missing axes default to 1).

    Axes with degree 1 are still materialised so sharding specs can always
    name every axis regardless of the configured topology.

    dcn_degrees: multi-slice topology (SURVEY §7.1 ProcessGroup row /
    §7.3 multi-slice; reference counterpart is the multi-node launch +
    master rendezvous, launch/controllers/master.py). Each named axis's
    total degree becomes dcn_degree * ici_degree with the DCN part as the
    slow (outer) component, so collectives over the axis's inner part ride
    ICI within one slice and only the outer part crosses the
    data-center network. E.g. degrees={'dp': 2, 'mp': 4},
    dcn_degrees={'dp': 2} on 2 slices of 4 chips: mp stays intra-slice,
    dp spans slices.
    """
    devices = list(devices if devices is not None else jax.devices())
    full = {ax: int(degrees.get(ax, 1)) for ax in axis_order}
    extra = [k for k in degrees if k not in full]
    axis_names = tuple(axis_order) + tuple(extra)
    for k in extra:
        full[k] = int(degrees[k])

    if dcn_degrees:
        bad = [k for k in dcn_degrees if k not in axis_names]
        if bad:
            raise ValueError(f"unknown dcn axes {bad}")
        dcn = {ax: int(dcn_degrees.get(ax, 1)) for ax in axis_names}
        total = {ax: full[ax] * dcn[ax] for ax in axis_names}
        n = math.prod(total.values())
        if n > len(devices):
            raise ValueError(
                f"mesh degrees {total} need {n} devices, have "
                f"{len(devices)}")
        # group devices by slice: real TPU slices expose slice_index;
        # the virtual CPU mesh (and single-slice platforms) fall back to
        # contiguous equal blocks — device order from jax.devices() is
        # already slice-major on multi-slice systems.
        devs = devices[:n]
        dcn_shape = tuple(dcn[ax] for ax in axis_names)
        ici_shape = tuple(full[ax] for ax in axis_names)
        arr = np.asarray(devs, dtype=object).reshape(dcn_shape + ici_shape)
        # interleave [dcn_0, ici_0, dcn_1, ici_1, ...] then merge pairs,
        # making DCN the outer component of every named axis
        k = len(axis_names)
        order = [i for pair in ((d, d + k) for d in range(k)) for i in pair]
        arr = arr.transpose(order).reshape(
            tuple(total[ax] for ax in axis_names))
        return Mesh(arr, axis_names)

    n = math.prod(full.values())
    if n > len(devices):
        raise ValueError(
            f"mesh degrees {full} need {n} devices, have {len(devices)}")
    shape = tuple(full[ax] for ax in axis_names)
    arr = np.asarray(devices[:n], dtype=object).reshape(shape)
    return Mesh(arr, axis_names)


def set_mesh(mesh: Mesh) -> None:
    global _global_mesh
    _global_mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return _global_mesh


def ambient_concrete_mesh() -> Optional[Mesh]:
    """The concrete mesh ``jax.set_mesh`` installed, or None: what
    keeps ``with jax.set_mesh(mesh):`` a sufficient spelling for
    consumers that otherwise read the paddle global."""
    try:
        mesh = jax.sharding.get_mesh()
    except ValueError:  # under jit only the abstract mesh is visible
        return None
    return mesh if mesh.axis_names else None


class _UseMesh:
    """What :func:`use_mesh` returns: as a plain statement it installs
    ``mesh`` as the paddle global immediately; as a context manager it
    additionally enters the jax ``Mesh`` context and restores the
    previous paddle global on exit."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._prev = get_mesh()
        self._entered = False
        set_mesh(mesh)

    def __enter__(self):
        # an AbstractMesh has no context manager — the paddle global
        # alone is what device-free analysis reads
        if hasattr(self.mesh, "__enter__"):
            self.mesh.__enter__()
            self._entered = True
        return self.mesh

    def __exit__(self, *exc):
        global _global_mesh
        if self._entered:
            self.mesh.__exit__(*exc)
        _global_mesh = self._prev
        return False


def use_mesh(mesh: Mesh) -> "_UseMesh":
    """Install ``mesh`` as the paddle global (and, used as a context
    manager, the jax ``Mesh`` context for the duration)."""
    return _UseMesh(mesh)


def ensure_mesh() -> Mesh:
    """Return the global mesh, building a pure-DP one if none was set."""
    global _global_mesh
    if _global_mesh is None:
        set_mesh(build_mesh({"dp": len(jax.devices())}))
    return _global_mesh


def fake_mesh(degrees: Dict[str, int],
              axis_order: Sequence[str] = HYBRID_AXES):
    """Device-free mesh for ahead-of-time analysis: an
    `jax.sharding.AbstractMesh` with the hybrid axis order, buildable on
    a machine with ONE device (or none). `analysis.shard_lint` traces
    under it; it can also be `set_mesh()`-installed so Group/axis_degree
    introspection resolves without hardware. Unlike build_mesh, missing
    axes are NOT padded to degree 1 — the analyzer should see exactly
    the axes the plan names."""
    from jax.sharding import AbstractMesh
    names = [ax for ax in axis_order if ax in degrees]
    names += [ax for ax in degrees if ax not in axis_order]
    return AbstractMesh(tuple(int(degrees[ax]) for ax in names),
                        tuple(names))


def mesh_axis_sizes(mesh=None) -> Dict[str, int]:
    """{axis: degree} for a concrete Mesh OR AbstractMesh (introspection
    helper shared by shard_lint and the cost model)."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        return {}
    shape = getattr(mesh, "shape", None)
    if shape is not None and hasattr(shape, "items"):
        return {str(k): int(v) for k, v in shape.items()}
    return {ax: int(d) for ax, d in zip(mesh.axis_names,
                                        mesh.devices.shape)}


def axis_degree(name: str) -> int:
    mesh = get_mesh()
    if mesh is None:
        # `with jax.set_mesh(...)` installs only jax's ambient context;
        # the TP layer selection must see the same topology there
        mesh = ambient_concrete_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh_axis_sizes(mesh).get(name, 1)


def data_axes(mesh: Optional[Mesh] = None) -> List[str]:
    """Axes the global batch is sharded over (dp + sharding)."""
    mesh = mesh or ensure_mesh()
    sizes = mesh_axis_sizes(mesh)
    return [ax for ax in ("dp", "sharding")
            if sizes.get(ax, 1) > 1] or ["dp"]


class CommunicateTopology:
    """Cartesian rank-coordinate helper, reference topology.py:70.

    On TPU ranks are device indices in the global mesh; this exists for
    API parity and for the launcher/debug tooling.
    """

    def __init__(self, hybrid_group_names=None, dims=None):
        self._axes = list(hybrid_group_names or HYBRID_AXES)
        self._dims = list(dims or [axis_degree(a) for a in self._axes])

    def get_hybrid_group_names(self):
        return list(self._axes)

    def get_dim(self, axis_name):
        return self._dims[self._axes.index(axis_name)]

    def world_size(self):
        return math.prod(self._dims)

    def get_rank(self, **coords) -> int:
        assert sorted(coords) == sorted(self._axes)
        rank = 0
        for ax, dim in zip(self._axes, self._dims):
            rank = rank * dim + coords[ax]
        return rank

    def get_coord(self, rank: int):
        coords = []
        for dim in reversed(self._dims):
            coords.append(rank % dim)
            rank //= dim
        return dict(zip(self._axes, reversed(coords)))

    def get_axis_list(self, axis_name: str, index: int) -> List[int]:
        """All global ranks whose coordinate on `axis_name` == index."""
        out = []
        for r in range(self.world_size()):
            if self.get_coord(r)[axis_name] == index:
                out.append(r)
        return out


class HybridCommunicateGroup:
    """Paddle-shaped view of the hybrid topology
    (reference: fleet/base/topology.py:189).

    Exposes the same *_rank / *_world_size / *_group accessors fleet users
    call; "groups" are mesh axis names rather than NCCL communicators.
    """

    def __init__(self, topology: Optional[CommunicateTopology] = None):
        self._topo = topology or CommunicateTopology()
        self._mesh = ensure_mesh()
        # single-controller JAX: this process sees all devices; logical
        # rank-0 view unless a launcher set a per-process rank.
        from . import env
        self._global_rank = env.get_rank()

    # --- degrees -------------------------------------------------------
    def get_data_parallel_world_size(self):
        return axis_degree("dp")

    def get_model_parallel_world_size(self):
        return axis_degree("mp")

    def get_pipe_parallel_world_size(self):
        return axis_degree("pp")

    def get_sharding_parallel_world_size(self):
        return axis_degree("sharding")

    def get_sep_parallel_world_size(self):
        return axis_degree("sep")

    # --- ranks ---------------------------------------------------------
    def _coord(self):
        return self._topo.get_coord(
            self._global_rank % self._topo.world_size())

    def get_data_parallel_rank(self):
        return self._coord()["dp"]

    def get_model_parallel_rank(self):
        return self._coord()["mp"]

    def get_stage_id(self):
        return self._coord()["pp"]

    def get_sharding_parallel_rank(self):
        return self._coord()["sharding"]

    def get_sep_parallel_rank(self):
        return self._coord()["sep"]

    # --- groups (mesh axis names stand in for communicators) -----------
    def get_data_parallel_group(self):
        from .communication.group import Group
        return Group(axis_name="dp")

    def get_model_parallel_group(self):
        from .communication.group import Group
        return Group(axis_name="mp")

    def get_pipe_parallel_group(self):
        from .communication.group import Group
        return Group(axis_name="pp")

    def get_sharding_parallel_group(self):
        from .communication.group import Group
        return Group(axis_name="sharding")

    def get_sep_parallel_group(self):
        from .communication.group import Group
        return Group(axis_name="sep")

    def get_check_parallel_group(self, *a, **k):
        from .communication.group import Group
        return Group(axis_name=None)

    def topology(self):
        return self._topo

    def get_parallel_mode(self):
        if axis_degree("pp") > 1:
            return "pipeline"
        if axis_degree("sharding") > 1:
            return "sharding"
        if axis_degree("mp") > 1:
            return "model"
        return "data"


_hcg: Optional[HybridCommunicateGroup] = None


def set_hybrid_communicate_group(hcg) -> None:
    global _hcg
    _hcg = hcg


def get_hybrid_communicate_group() -> HybridCommunicateGroup:
    global _hcg
    if _hcg is None:
        _hcg = HybridCommunicateGroup()
    return _hcg

