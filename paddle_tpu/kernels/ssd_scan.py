"""The Mamba-2 scan (``nn/functional/ssm.py``) as Pallas TPU kernels, one
each way, joined by a ``custom_vjp``.

``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = C_t . S_t + D x_t``
a chunk of ``L`` tokens at a time.  A grid step is one (batch, group of
heads that share B and C, chunk); the chunk axis is last and sequential:

* **forward**: the group's state, float32 ``(N, R*P)``, lives in a VMEM
  scratch, zero at chunk 0 and carried down the grid.  In VMEM and nowhere
  else: ``scores = C B^T`` once a group, and a head at a time the
  ``(L, L)`` ``decay = exp(where(causal, cum_l - cum_s, -inf))`` and
  ``W = scores * decay * dt_s`` in front of ``W x``; for all the group's
  heads at once ``exp(cum_l) * (C state)`` and the state's update
  ``exp(total) * state + B^T (x * to_end)``;
* **backward**: the same grid walked from the last chunk to the first,
  carrying the state's cotangent.  It makes the ``(L, L)`` matrices again
  and writes dx, dB, dC (the group's heads summed inside the step) and, a
  token and head, the float32 gradients of ``dt``, of the log-decay and of
  ``D``.  The state that entered each chunk is a residual: the forward
  that is differentiated writes it beside y (float32, ``(B, S/L, N,
  H*P)``: 134 MB a layer at 8,192 x 64 x 64 x 128), measured at +0.05 ms
  a layer where a states-only sweep in front of the backward took 0.59;
* around them, in XLA on ``(B, S, H)`` float32 arrays: ``cumsum(dt a)``
  inside a chunk in front, the reverse cumulative sum and the reductions to
  ``a``'s and ``D``'s gradients behind.

Per-head vectors cross HBM with the tokens on lanes, ``(B, G, R, S)`` (a
minor axis of R = 8 would be padded sixteen times over); a kernel turns its
``(R, L)`` block over for what multiplies a row of an ``(L, L)`` matrix or
of x, and turns its per-token gradients back.  Heads narrower than a
lane tile (P = 64) are taken a tile at a time: a head's ``W`` meets the
whole 128-lane tile of x and a lane mask keeps its half — the MXU pass is
no emptier than a 64-wide one, and no slice is cut inside a tile.

Numerics are the ``jnp`` scan's: MXU operands in the activations' type,
float32 sums; ``dt``, ``cum``, every decay and the carried state float32,
the state rounded only as an MXU operand.  The carry replaces that scan's
all-pairs mix of chunk states by one scaled add a chunk.
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
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def supported(chunk: int, heads_per_group: int, p: int, n: int,
              interpret=None) -> bool:
    """Whether the kernels take a scan of these shapes: on a TPU or under
    ``flash_attention.interpret_scope()``; chunk and state in whole lane
    tiles, a group's heads in whole lane tiles, and a head either a whole
    number of tiles or a whole fraction of one."""
    if interpret is None:
        interpret = _fa._INTERPRET
    return bool((interpret or jax.default_backend() == "tpu")
                and chunk % _LANES == 0 and n % _LANES == 0
                and (heads_per_group * p) % _LANES == 0
                and (p % _LANES == 0 or _LANES % p == 0))


def _dot(a, b, ca, cb):
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               preferred_element_type=F32)


def _tiling(p):
    """(lanes of x taken at a time, heads in them)."""
    width = max(p, _LANES)
    return width, width // p


def _lane(rows, width):
    return jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)


def _expand(v, first, p):
    """v (rows, R) a head -> (rows, width): lane j holds head
    ``first + j // p``."""
    rows = v.shape[0]
    width, hpg = _tiling(p)
    out = jnp.broadcast_to(v[:, first:first + 1], (rows, width))
    for i in range(1, hpg):
        out = jnp.where(_lane(rows, width) >= i * p, jnp.broadcast_to(
            v[:, first + i:first + i + 1], (rows, width)), out)
    return out


def _head_sum(v, i, p):
    """Sum of the lanes of v (rows, width) that are the i-th head's of the
    width -> (rows, 1)."""
    if p < v.shape[1]:
        lane = _lane(*v.shape)
        v = jnp.where((lane >= i * p) & (lane < (i + 1) * p), v, 0.0)
    return jnp.sum(v, axis=1, keepdims=True)


def _positions(chunk):
    """(l, s) of every element of a (chunk, chunk) matrix."""
    return (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0),
            jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))


def _decay(causal, cumc, cumr, r):
    """exp(cum_l - cum_s) for s <= l, else 0: masked before the
    exponential, whose argument above the diagonal would be positive."""
    return jnp.exp(jnp.where(causal, cumc[:, r:r + 1] - cumr[r:r + 1, :],
                             -jnp.inf))


def _zero_at_first(ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        ref[...] = jnp.zeros_like(ref)


def _carry(st, entering, sl, first, x_f32, bm_t, to_end, etot, p, op):
    """``st[:, sl] = exp(total) entering[:, sl] + B^T (x * to_end)`` for
    the heads from ``first`` whose lanes are ``sl``; ``bm_t`` is B^T."""
    xs = (x_f32 * _expand(to_end, first, p)).astype(op)
    st[:, sl] = (_expand(etot, first, p) * entering[:, sl]
                 + _dot(bm_t, xs, 1, 0))


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, y_ref, *rest,
                p):
    """``rest``: the scratch state, and in front of it, where the backward
    will want it, a block that takes the state ENTERING the chunk."""
    st = rest[-1]
    _zero_at_first(st)
    op = x_ref.dtype
    bm, cm = b_ref[0], c_ref[0]
    dtr, cumr = dt_ref[0, 0], cum_ref[0, 0]              # (R, L)
    dtc, cumc = dtr.T, cumr.T                            # (L, R)
    chunk, heads = cumc.shape
    width, hpg = _tiling(p)
    at_l, at_s = _positions(chunk)
    causal = at_l >= at_s
    scores = _dot(cm, bm, 1, 1)                          # (L, L)
    entering = st[...]                                   # (N, R*P)
    if len(rest) == 2:
        rest[0][0, 0, 0] = entering
    from_state = _dot(cm, entering.astype(op), 1, 0)     # (L, R*P)
    last = cumc[-1:, :]
    ecum, etot = jnp.exp(cumc), jnp.exp(last)
    to_end = jnp.exp(last - cumc) * dtc
    bm_t = bm.T                                          # turned over once
    for grp in range(heads // hpg):
        first, sl = grp * hpg, slice(grp * width, (grp + 1) * width)
        x_g = x_ref[0, :, sl]
        y = None
        for i in range(hpg):
            r = first + i
            w = (scores * _decay(causal, cumc, cumr, r)
                 * dtr[r:r + 1, :]).astype(op)
            y_r = _dot(w, x_g, 1, 0)
            y = y_r if y is None else jnp.where(
                _lane(chunk, width) >= i * p, y_r, y)
        x_f32 = x_g.astype(F32)
        y = (y + _expand(ecum, first, p) * from_state[:, sl]
             + _expand(d_ref[0], first, p) * x_f32)
        y_ref[0, :, sl] = y.astype(op)
        _carry(st, entering, sl, first, x_f32, bm_t, to_end, etot, p, op)


def _bwd_kernel(x_ref, dy_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, s0_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref, dd_ref, dst, *,
                p):
    """One chunk's backward given the cotangent ``dst`` of the state that
    LEAVES it.  With G = C B^T, M the decay, W = G M dt_s, u_s = to_end:

      y  = W x + e^{cum_l} (C S0) + D x       S1 = e^{total} S0 + B^T (u x)

    ``dt_s`` enters only as ``dt_s e^{-cum_s}``, so the log-decay's
    gradient at a SOURCE position is ``-dt_s`` times ``dt_s``'s own: the
    kernel writes the latter (``ddt``: from W, and from u) and the former's
    TARGET side alone (``dcum``), and XLA joins them."""
    _zero_at_first(dst)
    op = x_ref.dtype
    bm, cm = b_ref[0], c_ref[0]
    dtr, cumr = dt_ref[0, 0], cum_ref[0, 0]              # (R, L)
    dtc, cumc = dtr.T, cumr.T                            # (L, R)
    chunk, heads = cumc.shape
    width, hpg = _tiling(p)
    at_l, at_s = _positions(chunk)
    causal, eye = at_l >= at_s, at_l == at_s
    s0, ds1 = s0_ref[0, 0, 0], dst[...]                  # (N, R*P) f32
    s0_op, ds1_op = s0.astype(op), ds1.astype(op)
    scores = _dot(cm, bm, 1, 1)                          # (L, L)
    from_state = _dot(cm, s0_op, 1, 0)                   # (L, R*P): C S0
    into_state = _dot(bm, ds1_op, 1, 0)                  # (L, R*P): B dS1
    last = cumc[-1:, :]
    ecum, etot = jnp.exp(cumc), jnp.exp(last)
    to_end_rate = jnp.exp(last - cumc)                   # u_s / dt_s
    to_end = to_end_rate * dtc
    head_lane = _lane(chunk, heads)
    cm_t = cm.T                                          # turned over once
    # a token and head, tokens on sublanes, a column a head: what W gave
    # cum_l; sum_p dy (C S0); sum_p x (B dS1)
    dcum_w = dy_state = x_state = jnp.zeros((chunk, heads), F32)
    decayed = jnp.zeros((1, heads), F32)     # sum of dS1 * S0, a head
    dscores = jnp.zeros((chunk, chunk), F32)
    dc = jnp.zeros(cm.shape, F32)
    db = jnp.zeros(bm.shape, F32)
    for grp in range(heads // hpg):
        first, sl = grp * hpg, slice(grp * width, (grp + 1) * width)
        x_g, dy_g = x_ref[0, :, sl], dy_ref[0, :, sl]
        x_f32, dy_f32 = x_g.astype(F32), dy_g.astype(F32)
        lane = _lane(chunk, width)
        sums = (dy_f32 * from_state[:, sl], x_f32 * into_state[:, sl],
                jnp.sum(ds1[:, sl] * s0[:, sl], axis=0, keepdims=True))
        dx = None
        for i in range(hpg):
            r = first + i
            decay = _decay(causal, cumc, cumr, r)
            gm = scores * decay
            w = (gm * dtr[r:r + 1, :]).astype(op)
            mine = (lane >= i * p) & (lane < (i + 1) * p)
            x_r = x_g if hpg == 1 else jnp.where(mine, x_g,
                                                 jnp.zeros((), op))
            dw = _dot(dy_g, x_r, 1, 1)                   # (L, L): dy x_r^T
            k = dw * gm
            # D's gradient a token, sum_p dy x, is dw's diagonal (products
            # of two operand-type numbers are exact in float32)
            dd_ref[0, 0, r:r + 1, :] = jnp.sum(
                jnp.where(eye, dw, 0.0), axis=0, keepdims=True)
            # dt_s's own gradient through W: tokens on lanes
            ddt_ref[0, 0, r:r + 1, :] = jnp.sum(k, axis=0, keepdims=True)
            dscores = dscores + dw * (decay * dtr[r:r + 1, :])
            dx_r = _dot(w, dy_g, 0, 0)                   # (L, width): W^T dy
            dx = dx_r if dx is None else jnp.where(mine, dx_r, dx)
            here = head_lane == r
            dcum_w = jnp.where(here, jnp.sum(
                k * dtr[r:r + 1, :], axis=1, keepdims=True), dcum_w)
            dy_state = jnp.where(here, _head_sum(sums[0], i, p),
                                 dy_state)
            x_state = jnp.where(here, _head_sum(sums[1], i, p), x_state)
            decayed = jnp.where(here[:1], _head_sum(sums[2], i, p),
                                decayed)
        to_end_g = _expand(to_end, first, p)
        dx = (dx + _expand(d_ref[0], first, p) * dy_f32
              + to_end_g * into_state[:, sl])
        dx_ref[0, :, sl] = dx.astype(op)
        dy_scaled = (dy_f32 * _expand(ecum, first, p)).astype(op)
        xs = (x_f32 * to_end_g).astype(op)
        dc = dc + _dot(dy_scaled, s0_op[:, sl], 1, 1)
        db = db + _dot(xs, ds1_op[:, sl], 1, 1)
        dst[:, sl] = (_expand(etot, first, p) * ds1[:, sl]
                      + _dot(cm_t, dy_scaled, 1, 0))
    # total = cum at the chunk's last position takes what the state's own
    # decay and every to_end gave it
    dtotal = (jnp.sum(x_state * to_end, axis=0, keepdims=True)
              + etot * decayed)                          # (1, R)
    is_last = jax.lax.broadcasted_iota(
        jnp.int32, (chunk, heads), 0) == chunk - 1
    ddt_ref[0, 0] = ddt_ref[0, 0] + (x_state * to_end_rate).T
    dcum_ref[0, 0] = (dcum_w + ecum * dy_state
                      + jnp.where(is_last, dtotal, 0.0)).T
    dscores = dscores.astype(op)
    dc_ref[0] = (dc + _dot(dscores, bm, 1, 0)).astype(dc_ref.dtype)
    db_ref[0] = (db + _dot(dscores, cm, 0, 0)).astype(db_ref.dtype)


def _specs(chunk, rp, n, heads, flip):
    """Block specs of one (batch, group, chunk) step; ``flip`` is the
    number of chunks when the grid walks them last to first, else 0."""
    at = (lambda z: flip - 1 - z) if flip else (lambda z: z)
    return dict(
        x=pl.BlockSpec((1, chunk, rp), lambda bi, g, z: (bi, at(z), g)),
        bc=pl.BlockSpec((1, chunk, n), lambda bi, g, z: (bi, at(z), g)),
        head=pl.BlockSpec((1, 1, heads, chunk),
                          lambda bi, g, z: (bi, g, 0, at(z))),
        d=pl.BlockSpec((1, 1, heads), lambda bi, g, z: (g, 0, 0)),
        state=pl.BlockSpec((1, 1, 1, n, rp),
                           lambda bi, g, z: (bi, g, at(z), 0, 0)))


def _dims(x3, b3, dt4, chunk):
    bsz, s, _ = x3.shape
    _, groups, heads, _ = dt4.shape
    return bsz, groups, heads, b3.shape[2] // groups, s // chunk


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _forward(x3, b3, c3, dt4, cum4, d3, p, chunk, keep_states, interpret):
    """y, and with ``keep_states`` the (B, G, chunks, N, R*P) float32
    states that entered the chunks."""
    bsz, groups, heads, n, nz = _dims(x3, b3, dt4, chunk)
    sp = _specs(chunk, heads * p, n, heads, 0)
    out_specs = [sp["x"]] + [sp["state"]] * keep_states
    out_shape = [jax.ShapeDtypeStruct(x3.shape, x3.dtype)] + [
        jax.ShapeDtypeStruct((bsz, groups, nz, n, heads * p), F32)
    ] * keep_states
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p),
        grid=(bsz, groups, nz),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["head"], sp["head"],
                  sp["d"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, heads * p), F32)],
        compiler_params=_PARAMS, name="ssd_scan_fwd", interpret=interpret,
    )(x3, b3, c3, dt4, cum4, d3)


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _backward(x3, dy3, b3, c3, dt4, cum4, d3, s0, p, chunk, interpret):
    bsz, groups, heads, n, nz = _dims(x3, b3, dt4, chunk)
    sp = _specs(chunk, heads * p, n, heads, nz)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p),
        grid=(bsz, groups, nz),
        in_specs=[sp["x"], sp["x"], sp["bc"], sp["bc"], sp["head"],
                  sp["head"], sp["d"], sp["state"]],
        out_specs=[sp["x"], sp["bc"], sp["bc"], sp["head"], sp["head"],
                   sp["head"]],
        out_shape=[like(x3), like(b3), like(c3), like(dt4), like(dt4),
                   like(dt4)],
        scratch_shapes=[pltpu.VMEM((n, heads * p), F32)],
        compiler_params=_PARAMS, name="ssd_scan_bwd", interpret=interpret,
    )(x3, dy3, b3, c3, dt4, cum4, d3, s0)


def _per_head(t, groups):
    """(B, S, H) -> (B, G, R, S): a head's tokens along the lanes."""
    bsz, s, h = t.shape
    return t.transpose(0, 2, 1).reshape(bsz, groups, h // groups, s)


def _operands(x, dt, a, b, c, d, chunk):
    """The kernels' operands: x, B, C as (B, S, width), and a head and
    token ``dt`` and the log-decay from each chunk's start to each of its
    positions, inclusive."""
    bsz, s, h, p = x.shape
    groups = b.shape[2]
    cum = jnp.cumsum((dt * a).reshape(bsz, s // chunk, chunk, h),
                     axis=2).reshape(bsz, s, h)
    return (x.reshape(bsz, s, h * p), b.reshape(bsz, s, -1),
            c.reshape(bsz, s, -1), _per_head(dt, groups),
            _per_head(cum, groups), d.reshape(groups, 1, h // groups))


def _run_forward(args, chunk, keep_states, interpret):
    x = args[0]
    with x64_scope(False):
        y, *states = _forward(*_operands(*args, chunk), x.shape[3], chunk,
                              keep_states, interpret)
    return (y.reshape(x.shape), *states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def ssd_scan(x, dt, a, b, c, d, chunk, interpret):
    """x (B, S, H, P), dt (B, S, H) f32, a, d (H,) f32, b, c (B, S, G, N),
    S a multiple of ``chunk`` -> y (B, S, H, P) in x's type."""
    return _run_forward((x, dt, a, b, c, d), chunk, False, interpret)[0]


def _ssd_scan_fwd(*args):
    *operands, chunk, interpret = args
    y, states = _run_forward(operands, chunk, True, interpret)
    return y, (*operands, states)


def _ssd_scan_bwd(chunk, interpret, residuals, dy):
    x, dt, a, b, c, d, states = residuals
    bsz, s, h, p = x.shape
    with x64_scope(False):
        x3, b3, c3, dt4, cum4, d3 = _operands(x, dt, a, b, c, d, chunk)
        dx, db, dc, ddt, dcum, dd = _backward(
            x3, dy.astype(x.dtype).reshape(x3.shape), b3, c3, dt4, cum4, d3,
            states, p, chunk, interpret)
        per_token = lambda t: t.reshape(bsz, h, s).transpose(0, 2, 1)
        ddt = per_token(ddt)
        # cum_l = sum of (dt a) over the chunk's positions up to l
        dcum = (per_token(dcum) - dt * ddt).reshape(bsz, s // chunk, chunk,
                                                    h)
        drate = jax.lax.cumsum(dcum, axis=2, reverse=True).reshape(bsz, s, h)
        return (dx.reshape(x.shape), ddt + a * drate,
                jnp.sum(dt * drate, axis=(0, 1)), db.reshape(b.shape),
                dc.reshape(c.shape), jnp.sum(dd, axis=(0, 3)).reshape(h))


# under ``jax.checkpoint`` (a recomputed block) the first forward keeps no
# residual, and a ``pallas_call`` has no rule that drops an unused output:
# ``optimize_remat`` runs the primal there, which writes no states
ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd, optimize_remat=True)
