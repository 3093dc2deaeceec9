"""What stands in front of a recurrent scan (``nn/functional/ssm.py::
conv_split_raw``) as Pallas TPU kernels, one each way, joined by a
``custom_vjp``: the causal depthwise convolution over the columns of a
fused projection, its bias and SiLU, the split of the result into the
scan's operands and, for a part that asks, the L2 normalisation of each
head.

The projection's buffer ``(B, S, W)`` is read where the GEMM left it: a
kernel is launched once a part (q, k, v of a Gated DeltaNet; x, B, C of a
Mamba-2 mixer), a grid step is one (batch, tile of the part's channels,
block of tokens), and the block index maps pick the tiles out of the wide
buffer, the taps and the bias, so no slice of any of them is written.  The
``taps - 1`` rows in front of a block come as a second, 16-row block of the
same operand (zeros in front of the row's first block).  Each part leaves
as one flat ``(B, S, width)`` array, which is how ``kernels/ssd_scan.py``
and ``kernels/delta_rule.py`` read their operands.

* **forward**, in VMEM, a lane group (a head, or one 128-lane tile) at a
  time, in a loop, so that the kernel's body is traced once, and within it
  a span of 128 rows at a time, so that a span's arrays stay near the
  vector registers: the taps in float32 on sublane rotations of the span, bias,
  SiLU, rounded to the activations' type; then, for a normalised part,
  ``scale * y / sqrt(sum y^2 + eps)`` over the head's lanes in float32,
  rounded once more: the roundings of ``causal_conv1d_raw`` and
  ``l2_normalize_raw``;
* **backward**: the same grid with the token blocks walked last to first.
  It makes the pre-activation and the norm's statistics again from the
  projection's block (the only residual is the projection's buffer
  itself), carries the pre-activation's cotangent of the rows after the
  block in a VMEM scratch, and writes the gradient of the part's columns
  and, as float32 accumulators that stay resident down the batch and
  token axes, the taps' and the bias's.

The kernels are bound by the VPU, not by bytes (the compiler's bundles:
``tools/mixer_conv_probe.py --bundles``), hence the logistic function as a
hyperbolic tangent (no float32 quotient).  Numerics are the ``jnp`` path's
to float32 rounding; the backward keeps the cotangent between the
normalisation and the SiLU in float32, where the ``jnp`` path rounds it to
the activations' type.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.dtype import x64_scope
from . import flash_attention as _fa

F32 = jnp.float32
_LANES = 128
#: rows of the block in front of a grid step's own: one bf16 tile
_HALO = 16
#: rows of the pre-activation's cotangent carried up the row: one f32 tile
_CARRY = 8
#: the most taps whose rows in front fit the carried tile
_MAX_TAPS = _CARRY
#: the most tokens and channels a grid step takes
_BLOCK_TOKENS = 512
_BLOCK_CHANNELS = 512
#: the rows of a block whose arithmetic is done at a time
_SPAN = 128
#: ``l2_normalize_raw``'s epsilon
_EPSILON = 1e-6


def supported(seq: int, width: int, offset: int, parts, taps: int,
              interpret=None) -> bool:
    """Whether the kernels take these shapes: on a TPU or under
    ``flash_attention.interpret_scope()``; the offset and every part's
    width in whole 128-lane tiles inside the buffer's ``width``, a
    normalised head whole tiles, rows in whole 16-row tiles and at most
    ``_MAX_TAPS`` taps.  ``parts``: ``(width, head_dim or None, scale)``
    each."""
    if interpret is None:
        interpret = _fa._INTERPRET
    if not ((interpret or jax.default_backend() == "tpu")
            and seq % _HALO == 0 and 1 <= taps <= _MAX_TAPS):
        return False
    at = 0
    for part_width, head, _ in parts:
        unit = head or _LANES
        if unit % _LANES or not part_width or any(
                size % unit for size in (part_width, at, offset + at)):
            return False
        at += part_width
    return offset + at <= width


def _tile(limit, unit, *sizes):
    """The largest multiple of ``unit``, ``limit`` at most, that divides
    every one of ``sizes``; ``unit`` where none does."""
    for t in range(limit - limit % unit, unit, -unit):
        if all(s % t == 0 for s in sizes):
            return t
    return unit


def _rows(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def _delayed(x, front, taps):
    """``[x_{t-(taps-1)+j} for j in range(taps)]`` of a block x (rows,
    lanes) float32, ``front`` (_HALO, lanes) the rows before it: a sublane
    rotation, and the block's first tile mended from ``front``."""
    out = []
    for j in range(taps):
        d = taps - 1 - j
        if not d:
            out.append(x)
            continue
        turned = pltpu.roll(x, d, 0)
        first = jnp.where(_rows(front.shape) < d, pltpu.roll(front, d, 0),
                          turned[:_HALO])
        out.append(jnp.concatenate([first, turned[_HALO:]], axis=0)
                   if x.shape[0] > _HALO else first)
    return out


def _advanced(g, behind, e):
    """``g_{t+e}`` of a block g (rows, lanes) float32, ``behind`` (_CARRY,
    lanes) the rows after it, ``1 <= e < _CARRY``."""
    turned = pltpu.roll(g, g.shape[0] - e, 0)
    last = jnp.where(_rows(behind.shape) >= _CARRY - e,
                     pltpu.roll(behind, _CARRY - e, 0), turned[-_CARRY:])
    return jnp.concatenate([turned[:-_CARRY], last], axis=0)


def _spans(rows):
    """The first rows and the size of a block's spans: the rows whose
    arithmetic is done at a time, arrays of a few vector registers each,
    which stay in them."""
    size = _tile(_SPAN, _HALO, rows)
    return range(0, rows, size), size


def _for_lane_groups(width, head, body):
    """``body(lanes)`` for each lane group (a head, or one 128-lane tile)
    of a block's ``width`` lanes, in a loop: the body is traced and lowered
    once, a quarter of the kernel's text (what a step's set-up pays)."""
    step = head or _LANES

    def group(g, carry):
        body(pl.ds(pl.multiple_of(g * step, step), step))
        return carry

    jax.lax.fori_loop(0, width // step, group, 0)


def _pre_activation(x_ref, front_ref, w, b, at, size, lanes, first_block):
    """(delayed spans, ``sum_j w_j x_{t-(k-1)+j} + bias``) of the ``size``
    rows from ``at`` and the lanes ``lanes`` of a grid step's block,
    float32; ``w`` (taps, lanes), ``b`` (1, lanes) or None."""
    x = x_ref[0, at:at + size, lanes].astype(F32)
    if at:
        front = x_ref[0, at - _HALO:at, lanes].astype(F32)
    else:
        front = jnp.where(first_block, 0.0,
                          front_ref[0, :, lanes].astype(F32))
    delayed = _delayed(x, front, w.shape[0])
    pre = delayed[0] * w[0:1]
    for j in range(1, len(delayed)):
        pre = pre + delayed[j] * w[j:j + 1]
    if b is not None:
        pre = pre + b
    return delayed, pre


def _sigmoid(x):
    """``1 / (1 + exp(-x))`` as ``(1 + tanh(x / 2)) / 2``: one
    transcendental and no division (an exact float32 quotient is a dozen
    VPU operations an element, a third of the forward's)."""
    return 0.5 + 0.5 * jnp.tanh(0.5 * x)


def _silu(x):
    """``x * _sigmoid(x)``, with ``h = x / 2``: ``h + h tanh(h)``."""
    half = 0.5 * x
    return half + half * jnp.tanh(half)


def _fwd_kernel(x_ref, front_ref, w_ref, *rest, silu, head, scale):
    """``rest``: the bias's block where there is one, then the output's."""
    *bias, out_ref = rest
    first_block = pl.program_id(2) == 0
    starts, size = _spans(out_ref.shape[1])

    def group(lanes):
        w = w_ref[:, lanes]
        b = bias[0][:, lanes] if bias else None
        for at in starts:
            _, y = _pre_activation(x_ref, front_ref, w, b, at, size, lanes,
                                   first_block)
            if silu:
                y = _silu(y)
            y = y.astype(out_ref.dtype)
            if head:
                yf = y.astype(F32)
                y = (yf * (scale * jax.lax.rsqrt(jnp.sum(
                    yf * yf, axis=1, keepdims=True) + _EPSILON))
                     ).astype(out_ref.dtype)
            out_ref[0, at:at + size, lanes] = y

    _for_lane_groups(out_ref.shape[2], head, group)


def _bwd_kernel(do_ref, x_ref, front_ref, w_ref, *rest, silu, head, scale,
                has_bias):
    """``rest``: the bias's block, the projection's gradient's, the taps'
    accumulator, the bias's accumulator (the first and the last where there
    is a bias), and the scratch that carries the pre-activation's cotangent
    of the rows after the block."""
    refs = list(rest)
    b_ref = refs.pop(0) if has_bias else None
    dx_ref, dw_ref, *db, behind_ref = refs
    db_ref = db[0] if has_bias else None
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _():
        behind_ref[...] = jnp.zeros_like(behind_ref)

    @pl.when((step == 0) & (pl.program_id(1) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        if has_bias:
            db_ref[...] = jnp.zeros_like(db_ref)

    # the token blocks go last to first: the row's first block comes last
    first_block = step == pl.num_programs(2) - 1
    taps = w_ref.shape[0]
    starts, size = _spans(dx_ref.shape[1])

    def group(lanes):
        w = w_ref[:, lanes]
        b = b_ref[:, lanes] if has_bias else None
        behind = behind_ref[:, lanes]
        sums = [None] * (taps + has_bias)     # the taps' rows, the bias's
        # ... and so do a block's spans, each handing the one in front of
        # it its first rows' cotangent
        for at in reversed(starts):
            delayed, pre = _pre_activation(x_ref, front_ref, w, b, at, size,
                                           lanes, first_block)
            g = do_ref[0, at:at + size, lanes].astype(F32)
            if silu:
                sig = _sigmoid(pre)
                act = pre * sig
                slope = sig + act * (1.0 - sig)
            else:
                act = pre
            if head:
                y = act.astype(x_ref.dtype).astype(F32)
                r = jax.lax.rsqrt(jnp.sum(y * y, axis=1, keepdims=True)
                                  + _EPSILON)
                g = (scale * r) * (g - y * (r * r * jnp.sum(
                    g * y, axis=1, keepdims=True)))
            if silu:
                g = g * slope
            for j, over in enumerate(delayed + [None] * has_bias):
                term = jnp.sum(g if over is None else g * over, axis=0,
                               keepdims=True)
                sums[j] = term if sums[j] is None else sums[j] + term
            dx = g * w[taps - 1:taps]
            for j in range(taps - 1):
                dx = dx + _advanced(g, behind, taps - 1 - j) * w[j:j + 1]
            dx_ref[0, at:at + size, lanes] = dx.astype(dx_ref.dtype)
            behind = g[:_CARRY]
        behind_ref[:, lanes] = behind
        for j in range(taps):
            dw_ref[j:j + 1, lanes] += sums[j]
        if has_bias:
            db_ref[:, lanes] += sums[taps]

    _for_lane_groups(dx_ref.shape[2], head, group)


def _specs(at, tokens, channels, column, tap_column, taps, order):
    """Block specs of one grid step.  ``order`` turns the grid's indices
    into (batch, channel tile, token block); ``at`` a step of the token
    axis into the block it takes; ``column`` and ``tap_column`` are the
    part's first tile in the projection and in the taps."""
    ratio = tokens // _HALO

    def spec(block, index):
        return pl.BlockSpec(block, lambda *grid: index(*order(*grid)))
    return dict(
        x=spec((1, tokens, channels),
               lambda b, j, z: (b, at(z), column + j)),
        front=spec((1, _HALO, channels), lambda b, j, z: (
            b, jnp.maximum(at(z) * ratio - 1, 0), column + j)),
        taps=spec((taps, channels), lambda b, j, z: (0, tap_column + j)),
        bias=spec((1, channels), lambda b, j, z: (0, tap_column + j)),
        part=spec((1, tokens, channels), lambda b, j, z: (b, at(z), j)),
        dtaps=spec((taps, channels), lambda b, j, z: (0, j)),
        dbias=spec((1, channels), lambda b, j, z: (0, j)))


def _tiles(seq, offset, tap_offset, width, head, blocks):
    """(tokens, channels) of a part's grid step, ``blocks`` at most, and
    the part's first channel tile in the projection and in the taps."""
    channels = _tile(blocks[1], head or _LANES, width, offset, tap_offset)
    return (_tile(blocks[0], _HALO, seq), channels, offset // channels,
            tap_offset // channels)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _forward(proj, weight, bias, offset, parts, silu, blocks, interpret):
    """The parts, a launch each; ``weight`` (k, C) and ``bias`` (1, C) or
    None float32."""
    bsz, seq, _ = proj.shape
    outs, at = [], 0
    for width, head, scale in parts:
        bt, ct, column, tap_column = _tiles(seq, offset + at, at, width,
                                            head, blocks)
        sp = _specs(lambda z: z, bt, ct, column, tap_column,
                    weight.shape[0], lambda b, j, z: (b, j, z))
        operands = (proj, proj, weight) + (() if bias is None else (bias,))
        outs.append(pl.pallas_call(
            functools.partial(_fwd_kernel, silu=silu, head=head,
                              scale=scale),
            grid=(bsz, width // ct, seq // bt),
            in_specs=[sp["x"], sp["front"], sp["taps"]]
            + [sp["bias"]] * (bias is not None),
            out_specs=sp["part"],
            out_shape=jax.ShapeDtypeStruct((bsz, seq, width), proj.dtype),
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "parallel")),
            name="causal_conv_fwd", interpret=interpret)(*operands))
        at += width
    return tuple(outs)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _backward(douts, proj, weight, bias, offset, parts, silu, blocks,
              interpret):
    """A part's columns' gradient each, and the float32 gradients of the
    taps (k, C) and of the bias (1, C) (None without one)."""
    bsz, seq, _ = proj.shape
    taps = weight.shape[0]
    has_bias = bias is not None
    dxs, dws, dbs, at = [], [], [], 0
    for (width, head, scale), dout in zip(parts, douts):
        bt, ct, column, tap_column = _tiles(seq, offset + at, at, width,
                                            head, blocks)
        nz = seq // bt
        # the accumulators' blocks are a channel tile's, whatever batch and
        # token block: the tiles lead the grid
        sp = _specs(lambda z: nz - 1 - z, bt, ct, column, tap_column, taps,
                    lambda j, b, z: (b, j, z))
        operands = (dout, proj, proj, weight) + ((bias,) * has_bias)
        dx, dw, *db = pl.pallas_call(
            functools.partial(_bwd_kernel, silu=silu, head=head,
                              scale=scale, has_bias=has_bias),
            grid=(width // ct, bsz, nz),
            in_specs=[sp["part"], sp["x"], sp["front"], sp["taps"]]
            + [sp["bias"]] * has_bias,
            out_specs=[sp["part"], sp["dtaps"]] + [sp["dbias"]] * has_bias,
            out_shape=[jax.ShapeDtypeStruct((bsz, seq, width), proj.dtype),
                       jax.ShapeDtypeStruct((taps, width), F32)]
            + [jax.ShapeDtypeStruct((1, width), F32)] * has_bias,
            scratch_shapes=[pltpu.VMEM((_CARRY, ct), F32)],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "arbitrary", "arbitrary")),
            name="causal_conv_bwd", interpret=interpret)(*operands)
        dxs.append(dx)
        dws.append(dw)
        dbs += db
        at += width
    return (tuple(dxs), jnp.concatenate(dws, axis=1),
            jnp.concatenate(dbs, axis=1) if has_bias else None)


def _small(weight, bias):
    """The taps and the bias as the kernels take them: float32 (what the
    arithmetic is in; a few rows of a packed type are no block), the bias
    a row."""
    return (weight.astype(F32),
            None if bias is None else bias.astype(F32).reshape(1, -1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def conv_split(proj, weight, bias, offset, parts, silu, interpret):
    """proj (B, S, W), weight (k, C), bias (C,) or None, with ``C`` the sum
    of the parts' widths: the convolution of the columns ``offset .. offset
    + C`` of proj, then bias, SiLU and each part's normalisation -> a flat
    (B, S, width) array a part, in proj's type."""
    with x64_scope(False):
        return _forward(proj, *_small(weight, bias), offset, parts, silu,
                        (_BLOCK_TOKENS, _BLOCK_CHANNELS), interpret)


def _conv_split_fwd(proj, weight, bias, offset, parts, silu, interpret):
    return (conv_split(proj, weight, bias, offset, parts, silu, interpret),
            (proj, weight, bias))


def _conv_split_bwd(offset, parts, silu, interpret, residuals, douts):
    proj, weight, bias = residuals
    with x64_scope(False):
        dxs, dw, db = _backward(
            tuple(douts), proj, *_small(weight, bias), offset, parts, silu,
            (_BLOCK_TOKENS, _BLOCK_CHANNELS), interpret)
        after = proj.shape[2] - offset - weight.shape[1]
        dproj = jnp.pad(jnp.concatenate(dxs, axis=2),
                        ((0, 0), (0, 0), (offset, after)))
    return (dproj, dw.astype(weight.dtype),
            None if bias is None else db.reshape(bias.shape).astype(
                bias.dtype))


conv_split.defvjp(_conv_split_fwd, _conv_split_bwd)
