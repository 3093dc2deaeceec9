"""Collective communication API (reference surface:
python/paddle/distributed/collective.py — all_reduce:580, new_group:314,
split:1481 etc; kernels: paddle/fluid/operators/collective/ N19,
ProcessGroupNCCL N22).

TPU-native semantics: a collective is *data parallel code inside a
shard_map/pjit trace* — `all_reduce` is `lax.psum` over a mesh axis riding
ICI/DCN, not an NCCL ring kernel.  Outside any trace (plain eager,
single-process), collectives are identities over world_size-1 groups, which
matches reference behavior for a 1-rank group.

Group model: a group names a mesh axis (default axis: "dp"); under
shard_map the axis must be in scope.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..core.dispatch import call
from ..core.tensor import Tensor
from . import mesh as _mesh

__all__ = ["ReduceOp", "all_reduce", "all_gather", "reduce_scatter",
           "broadcast", "reduce", "scatter", "alltoall", "all_to_all",
           "send", "recv", "barrier", "new_group", "get_group",
           "wait", "split_group_axis"]


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


class Group:
    """A named communication group bound to a mesh axis."""

    def __init__(self, axis: str, ranks=None, gid=0):
        self.axis = axis
        self.ranks = ranks or []
        self.id = gid

    @property
    def nranks(self):
        return max(_mesh.axis_size(self.axis), 1)

    world_size = nranks

    @property
    def rank(self):
        try:
            return int(jax.lax.axis_index(self.axis))
        except NameError:
            return 0

    def __repr__(self):
        return f"Group(axis={self.axis}, nranks={self.nranks})"


_groups = {}
_default_axis = "dp"


def ensure_varying(arr, axis):
    """Promote a constant to device-varying for scan carries inside
    shard_map (vma typing); a value that already varies over ``axis`` is
    returned as is."""
    if axis in jax.typeof(arr).vma:
        return arr
    return jax.lax.pcast(arr, axis, to="varying")


def _axis_of(group) -> str:
    if group is None:
        return _default_axis
    if isinstance(group, Group):
        return group.axis
    if isinstance(group, str):
        return group
    ax = getattr(group, "axis", None)
    if ax is not None:
        return ax
    return _default_axis


def axis_in_trace(axis: str) -> bool:
    """PUBLIC: True when `axis` is bound as a manual mesh axis in the
    current shard_map/pmap trace (both directions pinned by
    tests/test_distributed.py).  Collective dispatch and the
    sequence-parallel attention routing key on this."""
    try:
        jax.lax.axis_index(axis)
        return True
    except NameError:
        return False
    except Exception:
        return False


_in_trace = axis_in_trace  # internal alias (historical name)


def new_group(ranks=None, backend=None, axis=None, timeout=None):
    gid = len(_groups) + 1
    g = Group(axis or _default_axis, ranks, gid)
    _groups[gid] = g
    return g


def get_group(gid=0):
    return _groups.get(gid) or Group(_default_axis)


def split_group_axis(axis: str):
    """Scope helper to retarget the default axis."""
    import contextlib

    @contextlib.contextmanager
    def ctx():
        global _default_axis
        prev = _default_axis
        _default_axis = axis
        try:
            yield
        finally:
            _default_axis = prev

    return ctx()


def _apply(tensor, raw, name):
    if isinstance(tensor, Tensor):
        out = call(raw, tensor, name=name)
        # paddle collectives are in-place on the input tensor
        tensor._array = out._array
        tensor._grad_node = out._grad_node
        tensor._out_index = out._out_index
        if out._grad_node is not None:
            tensor._stop_gradient = False
        return tensor
    return raw(tensor)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """reference: collective.py:580; kernel c_allreduce_op.h:348 → on TPU a
    single lax.psum over the group's mesh axis (XLA ICI collective)."""
    axis = _axis_of(group)

    def raw(x):
        if not _in_trace(axis):
            return x  # world of 1 outside shard_map
        if op == ReduceOp.SUM:
            return jax.lax.psum(x, axis)
        if op == ReduceOp.MAX:
            return jax.lax.pmax(x, axis)
        if op == ReduceOp.MIN:
            return jax.lax.pmin(x, axis)
        if op == ReduceOp.AVG:
            return jax.lax.pmean(x, axis)
        if op == ReduceOp.PROD:
            return jnp.exp(jax.lax.psum(jnp.log(x), axis))
        raise ValueError(f"op {op}")

    return _apply(tensor, raw, "all_reduce")


def all_gather(tensor_list, tensor=None, group=None, sync_op=True, axis=0):
    """reference: collective.py all_gather; c_allgather_op."""
    grp_axis = _axis_of(group)
    if tensor is None:
        tensor = tensor_list
        tensor_list = None

    as_list = tensor_list is not None

    def raw(x):
        if not _in_trace(grp_axis):
            return x[None] if as_list else x
        # list form stacks per-rank shards; tensor form concatenates on dim 0
        return jax.lax.all_gather(x, grp_axis, axis=0, tiled=not as_list)

    out = call(raw, tensor, name="all_gather")
    if as_list:
        from .. import ops
        parts = ops.unbind(out, 0)
        tensor_list.clear()
        tensor_list.extend(parts)
        return tensor_list
    return out


def all_gather_object(obj_list, obj, group=None):
    obj_list.clear()
    obj_list.append(obj)
    return obj_list


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """c_reducescatter_op → lax.psum_scatter."""
    axis = _axis_of(group)

    def raw(x):
        if not _in_trace(axis):
            return x
        return jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)

    src = tensor_list if tensor_list is not None else tensor
    if isinstance(src, (list, tuple)):
        from .. import ops
        src = ops.concat(list(src), axis=0)
    out = call(raw, src, name="reduce_scatter")
    if isinstance(tensor, Tensor):
        tensor._array = out._array
        return tensor
    return out


def broadcast(tensor, src=0, group=None, sync_op=True):
    """c_broadcast_op → under SPMD all shards already see src's value after
    an all_reduce of the masked value; in-trace uses axis_index masking."""
    axis = _axis_of(group)

    def raw(x):
        if not _in_trace(axis):
            return x
        idx = jax.lax.axis_index(axis)
        masked = jnp.where(idx == src, x, jnp.zeros_like(x))
        return jax.lax.psum(masked, axis)

    return _apply(tensor, raw, "broadcast")


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    axis = _axis_of(group)

    def raw(x):
        if not _in_trace(axis):
            return x
        summed = jax.lax.psum(x, axis) if op == ReduceOp.SUM else \
            jax.lax.pmax(x, axis) if op == ReduceOp.MAX else \
            jax.lax.pmin(x, axis)
        idx = jax.lax.axis_index(axis)
        return jnp.where(idx == dst, summed, x)

    return _apply(tensor, raw, "reduce")


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    axis = _axis_of(group)
    if tensor_list is None:
        return tensor

    def raw(stacked):
        if not _in_trace(axis):
            return stacked[src]
        idx = jax.lax.axis_index(axis)
        return jnp.take(stacked, idx, axis=0)

    from .. import ops
    stacked = ops.stack(list(tensor_list), axis=0)
    out = call(raw, stacked, name="scatter")
    if isinstance(tensor, Tensor):
        tensor._array = out._array
        return tensor
    return out


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    """global_scatter/gather sibling (c_alltoall) → lax.all_to_all."""
    axis = _axis_of(group)
    from .. import ops
    if isinstance(in_tensor_list, (list, tuple)):
        x = ops.stack(list(in_tensor_list), axis=0)
    else:
        x = in_tensor_list

    def raw(x):
        if not _in_trace(axis):
            return x
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=False)

    out = call(raw, x, name="alltoall")
    if out_tensor_list is not None:
        parts = ops.unbind(out, 0)
        out_tensor_list.clear()
        out_tensor_list.extend(parts)
        return out_tensor_list
    return out


all_to_all = alltoall


def all_to_all_single(in_tensor, out_tensor=None, in_split_sizes=None,
                      out_split_sizes=None, group=None, sync_op=True):
    axis = _axis_of(group)

    def raw(x):
        if not _in_trace(axis):
            return x
        n = jax.lax.axis_size(axis)
        resh = x.reshape((n, x.shape[0] // n) + x.shape[1:])
        out = jax.lax.all_to_all(resh, axis, split_axis=0, concat_axis=0,
                                 tiled=False)
        return out.reshape(x.shape)

    out = call(raw, in_tensor, name="all_to_all_single")
    if isinstance(out_tensor, Tensor):
        out_tensor._array = out._array
        return out_tensor
    return out


def send(tensor, dst=0, group=None, sync_op=True):
    """p2p send (send_v2). In-trace: expressed as ppermute with the matched
    recv (see parallel.pipeline for the paired usage)."""
    axis = _axis_of(group)

    def raw(x):
        if not _in_trace(axis):
            return x
        n = _mesh.axis_size(axis)
        return jax.lax.ppermute(x, axis, [(i, dst) for i in range(n)])

    return _apply(tensor, raw, "send")


def recv(tensor, src=0, group=None, sync_op=True):
    axis = _axis_of(group)

    def raw(x):
        if not _in_trace(axis):
            return x
        n = _mesh.axis_size(axis)
        return jax.lax.ppermute(x, axis, [(src, i) for i in range(n)])

    return _apply(tensor, raw, "recv")


def isend(tensor, dst=0, group=None):
    send(tensor, dst, group)
    return _DummyTask()


def irecv(tensor, src=0, group=None):
    recv(tensor, src, group)
    return _DummyTask()


class _DummyTask:
    def wait(self):
        return True

    def is_completed(self):
        return True


def barrier(group=None):
    """Execution barrier: on the XLA path programs are already bulk-
    synchronous; across processes use multihost sync when initialized."""
    try:
        import jax.experimental.multihost_utils as mh
        if jax.process_count() > 1:
            mh.sync_global_devices("paddle_tpu_barrier")
    except Exception:
        pass


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor) and hasattr(tensor._array, "block_until_ready"):
        tensor._array.block_until_ready()


def get_world_size(group=None):
    if group is not None:
        return _axis_size_or_world(_axis_of(group))
    try:
        return jax.process_count()
    except Exception:
        return 1


def _axis_size_or_world(axis):
    n = _mesh.axis_size(axis)
    return n if n > 1 else 1
