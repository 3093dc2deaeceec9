"""Flash attention (Pallas TPU).

Blockwise-softmax attention with O(S) memory — the capability the reference
lacks entirely (SURVEY.md §5.7: no flash/ring attention in the snapshot; its
fused FMHA paddle/fluid/operators/fused/fmha_ref.h is still O(S^2)).

Forward and backward are dedicated Pallas kernels (FlashAttention-2 style
custom_vjp; see flash_attention_pallas.py).
"""
from __future__ import annotations

import contextlib
import math

import jax

_DEFAULT_BLOCK_Q = 128
_DEFAULT_BLOCK_K = 128

#: True only inside :func:`interpret_scope`
_INTERPRET = False


@contextlib.contextmanager
def interpret_scope():
    """Run the kernel in the Pallas interpreter, on any backend, for every
    program TRACED inside the scope — how a test or a CPU rehearsal sends a
    whole model through the kernel path (the model's attention call has no
    ``interpret`` argument to pass).  Process-wide, not per thread: serving
    traces its programs on the scheduler thread.  Never entered by the
    library itself."""
    global _INTERPRET
    prev, _INTERPRET = _INTERPRET, True
    try:
        yield
    finally:
        _INTERPRET = prev


#: mesh axes that shard the batch / head dims of a (B, S, H, D) activation
#: in a GSPMD program (distributed/mesh.py AXIS_ORDER; 'sep' shards the
#: sequence and runs the ring path inside its own shard_map instead)
_BATCH_AXES = ("dp", "sdp")
_HEAD_AXES = ("mp",)


def note_score_elements(computed: int, causal: int) -> None:
    """Drive ``flash.score_elements{which}`` at trace time — one inc per
    causal kernel call traced, valued over all its heads (a compile-once
    program contributes once, like ``mp.overlap_chunks``).  Called by the
    Pallas module, which must stay registry-free itself."""
    try:
        from ..observability import registry as _reg
        ctr = _reg.counter("flash.score_elements", ("which",))
        ctr.labels(which="computed").inc(int(computed))
        ctr.labels(which="causal").inc(int(causal))
    except Exception:
        pass


def note_fwd_call(operands: str) -> None:
    """Drive ``flash.fwd_calls{operands}`` at trace time — one inc per
    forward call traced, ``operands`` how q, k and v reach the kernel:
    ``packed`` (three block index maps onto the fused projection's (b, s,
    3*h*d) output) or ``split`` (three arrays)."""
    try:
        from ..observability import registry as _reg
        _reg.counter("flash.fwd_calls",
                     ("operands",)).labels(operands=operands).inc()
    except Exception:
        pass


def note_bwd_call(path: str) -> None:
    """Drive ``flash.bwd_calls{path}`` at trace time — one inc per
    backward call traced, ``path`` the residency its shape chose
    (``resident``, ``merged`` or ``split``)."""
    try:
        from ..observability import registry as _reg
        _reg.counter("flash.bwd_calls", ("path",)).labels(path=path).inc()
    except Exception:
        pass


def _active_mesh():
    """The global mesh the GSPMD program is being traced for, or None for
    a one-device program and inside a shard_map (manual axes: the caller
    already holds per-shard operands)."""
    from ..distributed.mesh import multi_device_mesh
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return multi_device_mesh()


def _live_axes(mesh, names):
    """Those of ``names`` the mesh splits more than one way."""
    if mesh is None:
        return ()
    return tuple(a for a in names if mesh.shape.get(a, 1) > 1)


def _ways(mesh, axes) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def supported(q, k=None, interpret=None) -> bool:
    """Whether the Pallas path applies to (B, S, H, D) query/key.

    Restricted to square self-attention (s_q == s_k, both block-aligned):
    the kernel's causal mask is start-aligned and a ragged key tail would be
    silently dropped — cross/cached attention takes the XLA reference path.
    Under a multi-device mesh the kernel runs per shard (see
    :func:`flash_attention_bshd`), so the head-count limits are checked at
    the per-shard head count.  ``interpret`` (default: whether an
    :func:`interpret_scope` is active) admits non-TPU backends — the kernel
    then runs in the Pallas interpreter.
    """
    if q.ndim != 4:
        return False
    s, h, d = q.shape[1], q.shape[2], q.shape[3]
    if k is not None and k.shape[1] != s:
        return False
    return _shape_supported(s, h, d, interpret)


def _shape_supported(s, h, d, interpret) -> bool:
    """:func:`supported` of square self-attention over ``s`` tokens and
    ``h`` heads of ``d``."""
    import os
    if os.getenv("PADDLE_TPU_DISABLE_FLASH", "").lower() in ("1", "true",
                                                             "yes"):
        return False
    if interpret is None:
        interpret = _INTERPRET
    if not interpret and jax.default_backend() != "tpu":
        return False
    if s % _DEFAULT_BLOCK_Q or d not in (64, 128, 256):
        return False
    mesh = _active_mesh()
    head_ways = _ways(mesh, _live_axes(mesh, _HEAD_AXES))
    if h % head_ways == 0:
        h //= head_ways
    # the forward holds K+V VMEM-resident; very long sequences exceed the
    # budget and must take the XLA path
    from .flash_attention_pallas import max_supported_seq
    return s <= max_supported_seq(h, d)


def packed_supported(qkv, num_heads) -> bool:
    """Whether :func:`flash_attention_packed` applies to a fused
    projection's output, (B, S, 3*H*D) in ``[q | k | v]`` column order:
    where :func:`supported` admits its (B, S, H, D) parts, a part is whole
    lane-aligned column blocks (H*D % 128: a head group's block is then
    never the part's full width, which Mosaic asks of an unaligned one),
    and no head axis is live — the fused projection is column-sharded at
    3*H*D / mp, which is not a head boundary of ``[q | k | v]``, so a
    program under 'mp' keeps the slices (and its head re-deal)."""
    if qkv.ndim != 3 or qkv.shape[2] % (3 * num_heads):
        return False
    s, d = qkv.shape[1], qkv.shape[2] // (3 * num_heads)
    if (num_heads * d) % 128 or _live_axes(_active_mesh(), _HEAD_AXES):
        return False
    return _shape_supported(s, num_heads, d, None)


def _per_shard(kernel, operands, batch, heads, in_spec, out_spec):
    """``kernel(*operands)``, per shard under a multi-device mesh.

    A Mosaic custom call has no GSPMD partitioning rule, so in a program
    traced for a multi-device mesh the kernel is wrapped in a shard_map
    over the axes that shard batch ('dp', 'sdp') and heads ('mp'): every
    device runs the kernel on its own block and no collective feeds it.
    ``in_spec`` / ``out_spec`` make an operand's and the result's
    PartitionSpec from the live (batch axes, head axes).  A batch or head
    count the mesh does not divide raises — there is no quiet O(S^2) path
    behind these entries."""
    mesh = _active_mesh()
    batch_axes = _live_axes(mesh, _BATCH_AXES)
    head_axes = _live_axes(mesh, _HEAD_AXES)
    if not (batch_axes or head_axes):
        return kernel(*operands)
    if batch % _ways(mesh, batch_axes) or heads % _ways(mesh, head_axes):
        raise ValueError(
            "flash attention under mesh %s: batch %d / heads %d are not "
            "divisible by the %s x %s axes that shard them"
            % (dict(mesh.shape), batch, heads, batch_axes, head_axes))
    # check_vma=False: pallas_call outputs carry no varying-axes type of
    # their own, and nothing here is replicated across the mapped axes
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(in_spec(batch_axes or None, head_axes or None),)
        * len(operands),
        out_specs=out_spec(batch_axes or None, head_axes or None),
        check_vma=False)(*operands)


def flash_attention_bshd(q, k, v, causal=False, scale=None, interpret=None):
    """q,k,v: (B, S, H, D) -> (B, S, H, D) — native layout, no transposes.
    Under a multi-device mesh every device runs the kernel on its own
    (B/dp, S, H/mp, D) block (:func:`_per_shard`)."""
    from .flash_attention_pallas import flash_attention_bshd_native
    if interpret is None:
        interpret = _INTERPRET

    def kernel(q_, k_, v_):
        return flash_attention_bshd_native(q_, k_, v_, causal=causal,
                                           scale=scale, interpret=interpret)

    def spec(batch_axes, head_axes):
        return jax.sharding.PartitionSpec(batch_axes, None, head_axes, None)

    return _per_shard(kernel, (q, k, v), q.shape[0], q.shape[2], spec, spec)


def flash_attention_packed(qkv, num_heads, causal=False, scale=None,
                           interpret=None):
    """:func:`flash_attention_bshd` of the ``[q | k | v]`` column parts of
    qkv (B, S, 3*H*D) -> (B, S, H, D), the parts read where they lie: no
    slice pass in front of the kernel (201 MB read and written a layer at
    16 x 1,024 x 16 heads of 64).  Under a mesh that shards the batch every
    device runs the kernel on its own (B/dp, S, 3*H*D) block; for
    :func:`packed_supported` shapes only (a live head axis raises)."""
    from .flash_attention_pallas import flash_attention_packed_native
    if interpret is None:
        interpret = _INTERPRET
    if _live_axes(_active_mesh(), _HEAD_AXES):
        raise ValueError(
            "flash_attention_packed under a live %s axis: the fused "
            "projection's column shards are not head boundaries of "
            "[q | k | v]; slice and call flash_attention_bshd"
            % (_HEAD_AXES,))

    def kernel(qkv_):
        return flash_attention_packed_native(qkv_, num_heads, causal=causal,
                                             scale=scale, interpret=interpret)

    P = jax.sharding.PartitionSpec
    return _per_shard(kernel, (qkv,), qkv.shape[0], num_heads,
                      lambda batch_axes, _: P(batch_axes, None, None),
                      lambda batch_axes, _: P(batch_axes, None, None, None))


def flash_attention_bshd_with_lse(q, k, v, causal=False, scale=None,
                                  interpret=False):
    """(out, lse): lse is the base-e row logsumexp, (B, S, H) — the
    differentiable building block of the ring-attention inner."""
    from .flash_attention_pallas import \
        flash_attention_bshd_with_lse as _impl
    return _impl(q, k, v, causal=causal, scale=scale, interpret=interpret)
