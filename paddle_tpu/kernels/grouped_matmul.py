"""Grouped matrix product over the experts a chip holds (Pallas TPU).

``grouped_matmul(lhs, rhs, group_sizes)``: the rows of ``lhs`` (m, k) are
sorted by group, ``rhs`` (groups, k, n) holds one matrix a group, row r of
the result is ``lhs[r] @ rhs[group of r]``.  Rows past the groups' total
belong to no group: they come out ZERO and take no gradient (the kernels
never visit their tiles, so what lies there is masked, not multiplied).

On a TPU (and in a flash ``interpret_scope``, for a CPU rehearsal) the
product is the megablox kernel that ships with the installed JAX
(``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward and for the
rows' gradient, ``tgmm`` for the weights'), whose grid runs over the row
tiles the group sizes cover and no others; this module gives it tiles, a
``custom_vjp`` of its own and the mask.  ``jax.lax.ragged_dot`` is the same
product everywhere else (a CPU test), and was measured against it on the
chip (PERF.md section 6, PR 33): XLA's own grouped kernel took 2.9 times as
long forward and carries no scope into a trace.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

from ..core.dtype import x64_scope
from . import flash_attention as _fa

_ROW_TILES = (512, 128)


def _backend():
    # the package's ``gmm`` attribute is its custom_vjp wrapper, which
    # shadows the module of the same name
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _tile(dim: int) -> int:
    """A tile of whole 128-lane blocks for an axis of ``dim``: the one of
    512..1,024 that pads ``dim`` least (2,688 -> 896, three tiles; 1,856 ->
    640, three tiles and 3% of padding), the whole axis when it is short."""
    if dim <= 1024:
        return dim
    return min(range(512, 1025, 128), key=lambda t: (-dim % t, -t))


def _row_tile(rows: int):
    for t in _ROW_TILES:
        if rows % t == 0:
            return t
    return None


def _live_rows(x, group_sizes):
    """``x`` with the rows past the groups' total set to zero."""
    live = jnp.arange(x.shape[0], dtype=jnp.int32) < jnp.sum(group_sizes)
    return jnp.where(live[:, None], x, jnp.zeros((), x.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm(lhs, rhs, group_sizes, out_dtype, row_tile, interpret):
    k, n = rhs.shape[1], rhs.shape[2]
    with x64_scope(False):
        out = _backend().gmm(lhs, rhs, group_sizes, out_dtype,
                             (row_tile, _tile(k), _tile(n)),
                             interpret=interpret)
    return _live_rows(out, group_sizes)


def _gmm_fwd(lhs, rhs, group_sizes, out_dtype, row_tile, interpret):
    return (_gmm(lhs, rhs, group_sizes, out_dtype, row_tile, interpret),
            (lhs, rhs, group_sizes))


def _gmm_bwd(out_dtype, row_tile, interpret, residuals, grad):
    lhs, rhs, group_sizes = residuals
    k, n = rhs.shape[1], rhs.shape[2]
    backend = _backend()
    grad = grad.astype(lhs.dtype)
    with x64_scope(False):
        d_lhs = backend.gmm(grad, rhs, group_sizes, lhs.dtype,
                            (row_tile, _tile(n), _tile(k)),
                            transpose_rhs=True, interpret=interpret)
        d_rhs = backend.tgmm(lhs.swapaxes(0, 1), grad, group_sizes,
                             rhs.dtype, (row_tile, _tile(k), _tile(n)),
                             num_actual_groups=rhs.shape[0],
                             interpret=interpret)
    return _live_rows(d_lhs, group_sizes), d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def kernel_path(rows: int, interpret=None) -> bool:
    """Whether :func:`grouped_matmul` of ``rows`` rows runs the megablox
    kernels: on a TPU or under ``flash_attention.interpret_scope()``, for
    rows in whole tiles."""
    if interpret is None:
        interpret = _fa._INTERPRET
    return bool((interpret or jax.default_backend() == "tpu")
                and _row_tile(rows))


def grouped_matmul(lhs, rhs, group_sizes, out_dtype=None, interpret=None):
    """lhs (m, k) sorted by group, rhs (groups, k, n), group_sizes (groups,)
    int32 -> (m, n) in ``out_dtype`` (default: lhs's), float32 sums; rows
    past the groups' total are zero."""
    out_dtype = jnp.dtype(out_dtype or lhs.dtype)
    if interpret is None:
        interpret = _fa._INTERPRET
    if kernel_path(lhs.shape[0], interpret):
        return _gmm(lhs, rhs, group_sizes, out_dtype,
                    _row_tile(lhs.shape[0]), bool(interpret))
    out = jax.lax.ragged_dot(_live_rows(lhs, group_sizes), rhs, group_sizes,
                             preferred_element_type=out_dtype)
    return _live_rows(out, group_sizes)
