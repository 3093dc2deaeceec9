"""Mesh management — the TPU-native replacement for the reference's NCCL
ring registry (paddle/fluid/platform/collective_helper.h:71 NCCLCommContext:
ring_id -> comm) and fleet topology
(fleet/base/topology.py:52 CommunicateTopology / :133 HybridCommunicateGroup).

A named `jax.sharding.Mesh` axis plays the role of a comm ring; the global
mesh (set once per process) plays the role of the ring registry.  Axis order
follows the reference's fixed hybrid order ["data", "pipe", "sharding",
"sep", "model", "expert"] projected onto the axes actually requested.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# canonical axis order (outer..inner). DCN-crossing axes (dp/pp) outermost so
# tensor-parallel collectives ride ICI — SURVEY.md §5.8.
AXIS_ORDER = ("dp", "pp", "sdp", "sep", "mp", "ep")

_global_mesh: Optional[Mesh] = None


def init_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    """Create + install the global mesh.  axes e.g. {"dp": 2, "mp": 4}."""
    global _global_mesh
    devices = devices if devices is not None else jax.devices()
    names = [a for a in AXIS_ORDER if a in axes]
    extra = [a for a in axes if a not in AXIS_ORDER]
    names += extra
    sizes = [axes[a] for a in names]
    n = int(np.prod(sizes)) if sizes else 1
    if n > len(devices):
        raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
    dev_array = np.asarray(devices[:n]).reshape(sizes if sizes else (1,))
    _global_mesh = Mesh(dev_array, tuple(names) if names else ("dp",))
    return _global_mesh


def init_hybrid_mesh(dcn_axes: Dict[str, int], ici_axes: Dict[str, int],
                     devices=None) -> Mesh:
    """Multi-slice mesh: ``dcn_axes`` span slices over the data-center
    network, ``ici_axes`` stay within a slice's ICI fabric.

    The analogue of the reference's FleetExecutor cross-cluster pipelining
    (fleet_executor/, SURVEY.md N25) and ProcessGroupHeter's
    intra-NCCL/inter-RPC split (ProcessGroupHeter.h:64): communication-heavy
    axes (tensor/sequence/expert parallel) are laid out on ICI; only the
    bandwidth-light axes (data/pipeline) cross DCN.  Built with
    jax.experimental.mesh_utils.create_hybrid_device_mesh so the device
    order matches the physical slice topology; falls back to a plain mesh
    when all devices are one slice (CPU tests, single slice)."""
    global _global_mesh
    devices = devices if devices is not None else jax.devices()
    dcn_names = [a for a in AXIS_ORDER if a in dcn_axes] + \
        [a for a in dcn_axes if a not in AXIS_ORDER]
    ici_names = [a for a in AXIS_ORDER if a in ici_axes] + \
        [a for a in ici_axes if a not in AXIS_ORDER]
    overlap = set(dcn_names) & set(ici_names)
    if overlap:
        raise ValueError(f"axes cannot be both DCN and ICI: {sorted(overlap)}")
    dcn_shape = [dcn_axes[a] for a in dcn_names]
    ici_shape = [ici_axes[a] for a in ici_names]
    names = tuple(dcn_names + ici_names)
    sizes = dcn_shape + ici_shape
    # mesh_utils needs per-device slice topology (slice_index); CPU/mock
    # devices don't have it — those take the row-major fallback below
    has_slices = all(getattr(d, "slice_index", None) is not None
                     for d in devices)
    if has_slices:
        from jax.experimental import mesh_utils
        # contract: mesh_shape and dcn_mesh_shape must be the SAME rank;
        # pad each side with 1s so the result's shape is dcn_shape+ici_shape
        dev_array = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=[1] * len(dcn_shape) + ici_shape,
            dcn_mesh_shape=dcn_shape + [1] * len(ici_shape),
            devices=devices)
        _global_mesh = Mesh(dev_array.reshape(sizes), names)
    else:
        # single-slice / CPU-mesh fallback: row-major assignment with the
        # DCN axes outermost (they change slowest -> contiguous slices)
        n = int(np.prod(sizes)) if sizes else 1
        if n > len(devices):
            raise ValueError(f"mesh needs {n} devices, have {len(devices)}")
        _global_mesh = Mesh(np.asarray(devices[:n]).reshape(sizes), names)
    return _global_mesh


def get_mesh() -> Optional[Mesh]:
    return _global_mesh


def multi_device_mesh() -> Optional[Mesh]:
    """The global mesh when it spans more than one device, else None — what
    a trace-time dispatch asks to tell a GSPMD program from a one-device
    one (the host's chip count says nothing about the program)."""
    mesh = _global_mesh
    return mesh if mesh is not None and mesh.size > 1 else None


@contextlib.contextmanager
def mesh_scope(mesh: Mesh):
    """Temporarily install ``mesh`` as the global mesh (restored on exit).

    The serving engine's tensor-parallel entries trace under this scope so
    the model's ``with_sharding_constraint`` sites resolve the SERVING
    mesh (a private ``('mp',)`` mesh over the TP devices) instead of
    whatever training mesh the process may or may not have installed —
    without the engine ever mutating global state beyond its own traced
    calls."""
    global _global_mesh
    prev = _global_mesh
    _global_mesh = mesh
    try:
        yield mesh
    finally:
        _global_mesh = prev


def set_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh


def ensure_mesh() -> Mesh:
    global _global_mesh
    if _global_mesh is None:
        init_mesh({"dp": len(jax.devices())})
    return _global_mesh


def axis_size(name: str) -> int:
    mesh = get_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def named_sharding(*spec) -> NamedSharding:
    return NamedSharding(ensure_mesh(), PartitionSpec(*spec))


class CommunicateTopology:
    """reference parity: fleet/base/topology.py:52 — cartesian rank topology
    over named axes."""

    def __init__(self, hybrid_group_names=("data", "pipe", "sharding", "model"),
                 dims=(1, 1, 1, 1)):
        self._names = list(hybrid_group_names)
        self._dims = list(dims)
        self._world = int(np.prod(dims))
        self._coords = list(np.ndindex(*dims))
        self._coord_to_rank = {c: i for i, c in enumerate(self._coords)}

    def get_hybrid_group_names(self):
        return self._names

    def get_dim(self, name):
        return self._dims[self._names.index(name)]

    get_dim_size = get_dim

    def world_size(self):
        return self._world

    def get_rank(self, **kwargs):
        coord = tuple(kwargs[n] for n in self._names)
        return self._coord_to_rank[coord]

    def get_coord(self, rank):
        return self._coords[rank]

    def get_axis_list(self, axis_name, index):
        axis = self._names.index(axis_name)
        return [r for r, c in enumerate(self._coords) if c[axis] == index]

    def get_comm_list(self, axis_name):
        """All groups along axis_name (reference: topology.py get_comm_list)."""
        axis = self._names.index(axis_name)
        other_dims = [d for i, d in enumerate(self._dims) if i != axis]
        groups = []
        for other in np.ndindex(*other_dims):
            group = []
            for k in range(self._dims[axis]):
                coord = list(other)
                coord.insert(axis, k)
                group.append(self._coord_to_rank[tuple(coord)])
            groups.append(group)
        return groups

    def get_rank_from_stage(self, global_rank, **kwargs):
        coord = list(self.get_coord(global_rank))
        for name, idx in kwargs.items():
            coord[self._names.index(name)] = idx
        return self._coord_to_rank[tuple(coord)]


class HybridCommunicateGroup:
    """reference parity: fleet/base/topology.py:133.

    On TPU every "communication group" is a mesh axis name; this object maps
    the fleet nomenclature (dp/pp/sharding/mp groups, ranks within each) onto
    the global mesh and a virtual rank (process_index-major).
    """

    AXIS_MAP = {"data": "dp", "pipe": "pp", "sharding": "sdp", "model": "mp",
                "sep": "sep", "expert": "ep"}

    def __init__(self, topology: CommunicateTopology, global_rank: int = 0):
        self._topo = topology
        self.global_rank = global_rank
        self.nranks = topology.world_size()
        for name in topology.get_hybrid_group_names():
            setattr(self, f"_{name}_degree", topology.get_dim(name))

    # data parallel
    def get_data_parallel_rank(self):
        return self._topo.get_coord(self.global_rank)[
            self._topo._names.index("data")]

    def get_data_parallel_world_size(self):
        return self._topo.get_dim("data")

    def get_data_parallel_group(self):
        return _AxisGroup("dp", self._topo, "data", self.global_rank)

    def get_data_parallel_group_src_rank(self):
        return self._topo.get_axis_list(
            "data", 0)[0] if self.nranks else 0

    # model (tensor) parallel
    def get_model_parallel_rank(self):
        return self._topo.get_coord(self.global_rank)[
            self._topo._names.index("model")]

    def get_model_parallel_world_size(self):
        return self._topo.get_dim("model")

    def get_model_parallel_group(self):
        return _AxisGroup("mp", self._topo, "model", self.global_rank)

    def get_model_parallel_group_src_rank(self):
        return 0

    # pipeline
    def get_stage_id(self):
        return self._topo.get_coord(self.global_rank)[
            self._topo._names.index("pipe")]

    def get_pipe_parallel_world_size(self):
        return self._topo.get_dim("pipe")

    def get_pipe_parallel_group(self):
        return _AxisGroup("pp", self._topo, "pipe", self.global_rank)

    # sharding
    def get_sharding_parallel_rank(self):
        return self._topo.get_coord(self.global_rank)[
            self._topo._names.index("sharding")]

    def get_sharding_parallel_world_size(self):
        return self._topo.get_dim("sharding")

    def get_sharding_parallel_group(self):
        return _AxisGroup("sdp", self._topo, "sharding", self.global_rank)

    def get_parallel_mode(self):
        if self.get_model_parallel_world_size() > 1 or \
                self.get_pipe_parallel_world_size() > 1:
            return "hybrid"
        if self.get_sharding_parallel_world_size() > 1:
            return "sharding"
        return "data"

    def topology(self):
        return self._topo

    def get_global_rank(self):
        return self.global_rank


class _AxisGroup:
    """A communication group = one mesh axis (ring_id analogue)."""

    def __init__(self, axis, topo, topo_name, global_rank):
        self.axis = axis
        self._topo = topo
        self._name = topo_name
        self._global_rank = global_rank
        self.nranks = topo.get_dim(topo_name)
        coord = topo.get_coord(global_rank)
        self.rank = coord[topo._names.index(topo_name)]

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, global_rank):
        coord = self._topo.get_coord(global_rank)
        return coord[self._topo._names.index(self._name)]

    @property
    def ranks(self):
        idx = [i for i, n in enumerate(self._topo._names) if n != self._name]
        my = self._topo.get_coord(self._global_rank)
        return [r for r, c in enumerate(self._topo._coords)
                if all(c[i] == my[i] for i in idx)]
