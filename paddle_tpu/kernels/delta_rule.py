"""The gated delta rule (``nn/functional/linear_attn.py``) as Pallas TPU
kernels, one each way, joined by a ``custom_vjp``.

A grid step is one (batch, key head with its R value heads, block of 256
tokens); the block axis is last and sequential.  A block is taken a SPAN of
128 tokens at a time: the unit lower-triangular systems of a span's chunks
(two of 64) stand on the diagonal of one (128, 128) float32 matrix, so every
(C, C) array of the rule is whole lane tiles and every product of the solve
a whole MXU pass.  In VMEM and nowhere else, a value head::

    decay = exp(where(same chunk, s <= l, cum_l - cum_s, -inf))
    A     = strict(K K^T (.) decay (.) beta_l)          T = (I + A)^-1
    U     = T (beta V)        W = T (beta e^cum K)

``T`` by block forward substitution, exact for any such ``A``: the blocks
of 16 on the VPU, a row of every block at a time (``_base_inverses``: the
``jnp`` path's own substitution, the blocks side by side along the lanes),
then ``T <- T - T A_off T`` over blocks of 32, 64 .. C (``A_off`` what A
holds inside a block and outside its halves) by float32 products.  Then a
chunk at a time, with the state ``S0`` that enters it (a float32 ``(D,
R*P)`` VMEM scratch, zero at chunk 0, carried down the grid)::

    new = U - W S0        o = e^cum (Q S0) + tril(Q K^T (.) decay) new
    S1  = e^total S0 + (e^(total - cum) K)^T new

* **forward**: writes o; the forward that is differentiated also writes,
  for the backward, the state that entered each grid step (float32 ``(B,
  Hk, S / 256, D, R*P)``) and each span's ``T`` in the activations' type,
  which is how every product takes it;
* **backward**: the same grid walked from the last block to the first,
  carrying the state's cotangent.  It makes decay, A, U, W again, walks
  the block's chunks forward from the state that entered it (their
  entering states and ``new``), then backward, and writes dq, dk (the R
  value heads summed inside the step), dv and, a token and head, the
  float32 gradients of beta and of ``cum``.  ``dA = -T^T dT T^T`` with
  ``dT = dU (beta V)^T + dW (beta e^cum K)^T`` is taken as ``-(T^T dU) U^T
  - (T^T dW) W^T`` (``T (beta V) = U``): the two left factors are dv's and
  dk's own terms, so the system's gradient costs one product more and no
  inverse is made again;
* around them, in XLA on ``(B, S, Hv)`` float32 arrays: ``cumsum(g)``
  inside a chunk in front, the reverse cumulative sum to ``dg`` behind.

q and k are read as ``(1, block, D)`` blocks of the ``(B, S, Hk*D)`` buffer,
v and o as ``(1, block, R*P)`` of ``(B, S, Hv*P)``: no head-major copy.  g
and beta cross HBM with the tokens on lanes, ``(B, Hk, R, S)``; a kernel
turns its ``(R, span)`` blocks over, one (8, 128) tile, for what scales a
row.

Numerics are the ``jnp`` path's: MXU operands in the activations' type,
float32 sums; ``cum``, every decay, beta, the system, its inverse and the
carried state float32, the state rounded only as an MXU operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.dtype import x64_scope
from . import flash_attention as _fa
from .ssd_scan import _PARAMS, _dot, _per_head, _positions, _zero_at_first

F32 = jnp.float32
_LANES = 128
#: tokens a grid step takes (whole spans): the per-step cost is paid once,
#: and a step's four systems (two spans of two value heads) go through the
#: solve together
_BLOCK = 256
#: the side of the diagonal blocks inverted a row at a time
_BASE = 16


def supported(chunk: int, rep: int, d_k: int, d_v: int,
              interpret=None) -> bool:
    """Whether the kernels take a rule of these shapes: on a TPU or under
    ``flash_attention.interpret_scope()``; key and value heads of whole
    lane tiles; a chunk that is a power of two from 16 to a span of 128
    lanes; a key head's value heads few enough that their per-token
    vectors turn over as one (8, span) tile."""
    if interpret is None:
        interpret = _fa._INTERPRET
    return bool((interpret or jax.default_backend() == "tpu")
                and d_k % _LANES == 0 and d_v % _LANES == 0
                and chunk in (16, 32, 64, 128) and 1 <= rep <= 4)


def _same(at_l, at_s, size):
    """l and s fall in the same block of ``size`` (a power of two)."""
    shift = size.bit_length() - 1
    return (at_l >> shift) == (at_s >> shift)


def _dot32(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=F32)


def _base_inverses(systems, at_l, at_s):
    """``(I + a)^-1`` on the ``_BASE``-blocks of the diagonal of each of
    the float32 (n, n) ``systems`` (strictly lower triangular there), by
    forward substitution on the VPU, all systems in step.  A system's
    blocks stand side by side, (16, n): row i holds row i of every block.
    Column j of a block is spread over the block's lanes (a select, a roll
    to the block's first lane, four doubling rolls), and ``T[i] -= a[i, j]
    T[j]`` for all rows i at once, j = 0, 1 ..: row j is final when its
    turn comes, rows up to j see zeros of the strictly lower ``a``."""
    n = at_l.shape[0]
    diagonal = _same(at_l, at_s, _BASE)
    wides = []
    for a in systems:
        inside = jnp.where(diagonal, a, 0.0)
        wide = inside[:_BASE]
        for b in range(1, n // _BASE):
            wide = wide + inside[b * _BASE:(b + 1) * _BASE]
        wides.append(wide)
    wide = jnp.stack(wides)                              # (systems, 16, n)
    row = jax.lax.broadcasted_iota(jnp.int32, wide.shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, wide.shape, 2) & (_BASE - 1)
    t = jnp.where(row == lane, 1.0, 0.0)
    for j in range(_BASE - 1):
        spread = pltpu.roll(jnp.where(lane == j, wide, 0.0), (n - j) % n, 2)
        step = 1
        while step < _BASE:
            spread = spread + pltpu.roll(spread, step, 2)
            step *= 2
        t = t - spread * jnp.broadcast_to(t[:, j:j + 1], wide.shape)
    return [jnp.where(diagonal, jnp.concatenate([t[i]] * (n // _BASE),
                                                axis=0), 0.0)
            for i in range(len(systems))]


def _inverses(systems, at_l, at_s, chunk):
    """``(I + a)^-1`` of each float32 (n, n) ``a`` of ``systems``, strictly
    lower triangular inside each ``chunk``-block of its diagonal and zero
    elsewhere: block forward substitution, [[T11, 0], [-T22 A21 T11,
    T22]], from the blocks of 16 (``_base_inverses``) over those of 32,
    64 .. chunk, all of a size at once, float32 products; the systems go
    a level at a time, so that their products are there to interleave."""
    ts = _base_inverses(systems, at_l, at_s)
    size = 2 * _BASE
    while size <= chunk:
        inside = _same(at_l, at_s, size) & ~_same(at_l, at_s, size // 2)
        halves = [_dot32(t, jnp.where(inside, a, 0.0))
                  for t, a in zip(ts, systems)]
        ts = [t - _dot32(half, t) for t, half in zip(ts, halves)]
        size *= 2
    return ts


def _masks(n, chunk):
    """(l, s, same chunk and s <= l, same chunk and s < l) of every element
    of a span's (n, n) matrices."""
    at_l, at_s = _positions(n)
    same = _same(at_l, at_s, chunk)
    return at_l, at_s, same & (at_l >= at_s), same & (at_l > at_s)


def _shared(q_ref, k_ref, rows):
    """(q, k, K K^T, Q K^T) of a span: the key head's, whatever the value
    head."""
    q, k = q_ref[0, rows, :], k_ref[0, rows, :]
    n = q.shape[0]
    both = _dot(jnp.concatenate([k, q], axis=0), k, 1, 1)    # (2n, n)
    return q, k, both[:n], both[n:]


def _systems(cum_ref, beta_ref, rows, masks, shared):
    """The chunks' systems of a span, a value head each: the per-token
    columns, the decays and ``K K^T (.) decay``."""
    heads = cum_ref.shape[2]
    cumr = cum_ref[0, 0, :, rows]                        # (R, n)
    # tokens on sublanes: one (8, n) tile turned over
    pad = [jnp.zeros((8 - 2 * heads, cumr.shape[1]), F32)] * (heads < 4)
    cols = jnp.concatenate([cumr, beta_ref[0, 0, :, rows]] + pad,
                           axis=0).T                     # (n, 8)
    out = []
    for j in range(heads):
        cumc, beta = cols[:, j:j + 1], cols[:, heads + j:heads + j + 1]
        decay = jnp.exp(jnp.where(masks[2], cumc - cumr[j:j + 1, :],
                                  -jnp.inf))
        out.append(dict(cumc=cumc, beta=beta, decay=decay,
                        kd=shared[2] * decay))
    return out


def _span(x, tb, v, chunk, masks, shared):
    """What a span's chunks need of a value head whatever state enters
    them, from its system (``_systems``), its inverse ``tb`` in the operands' type
    and its (n, P) block of v."""
    at_l, _, causal, _ = masks
    q, k, _, qk = shared
    op = k.dtype
    n = at_l.shape[0]
    cumc, beta = x["cumc"], x["beta"]
    # the log-decay to its chunk's end of every token, and the chunks'
    # whole decays
    lasts = [jnp.sum(jnp.where(at_l[:, :1] == (i + 1) * chunk - 1, cumc,
                               0.0), axis=0, keepdims=True)
             for i in range(n // chunk)]                 # (1, 1) each
    last = lasts[-1]
    for i in range(n // chunk - 2, -1, -1):
        last = jnp.where(at_l[:, :1] < (i + 1) * chunk, lasts[i], last)
    ecum, eend = jnp.exp(cumc), jnp.exp(last - cumc)
    k32 = k.astype(F32)
    vb = (v.astype(F32) * beta).astype(op)
    kb = (k32 * (beta * ecum)).astype(op)
    return dict(
        x, tb=tb, ecum=ecum, eend=eend, totals=[jnp.exp(t) for t in lasts],
        v=v, u=_dot(tb, vb, 1, 0), w=_dot(tb, kb, 1, 0).astype(op),
        attn=jnp.where(causal, qk * x["decay"], 0.0),
        qe=(q.astype(F32) * ecum).astype(op), ke=(k32 * eend).astype(op))


def _fwd_kernel(q_ref, k_ref, v_ref, cum_ref, beta_ref, o_ref, *rest, p,
                chunk, n):
    """``rest``: the scratch state, and in front of it, where the backward
    will want them, the blocks that take the state ENTERING the step and
    the spans' inverses."""
    st = rest[-1]
    _zero_at_first(st)
    op = q_ref.dtype
    heads = cum_ref.shape[2]
    masks = _masks(n, chunk)
    cps = n // chunk
    if len(rest) == 3:
        rest[0][0, 0, 0] = st[...]
    for span in range(q_ref.shape[1] // n):
        rows = slice(span * n, (span + 1) * n)
        shared = _shared(q_ref, k_ref, rows)
        systems = _systems(cum_ref, beta_ref, rows, masks, shared)
        inverses = _inverses(
            [jnp.where(masks[3], x["kd"] * x["beta"], 0.0) for x in systems],
            masks[0], masks[1], chunk)
        # every head's state-free work in front of any head's walk: the
        # order the schedule came out shortest in
        xs = [_span(systems[j], inverses[j].astype(op),
                    v_ref[0, rows, j * p:(j + 1) * p], chunk, masks, shared)
              for j in range(heads)]
        for j, x in enumerate(xs):
            lanes = slice(j * p, (j + 1) * p)
            if len(rest) == 3:
                rest[1][0, 0, j, rows, :] = x["tb"]
            reads, news = [], []
            for i in range(cps):
                part = slice(i * chunk, (i + 1) * chunk)
                s0 = st[:, lanes]                        # (D, P) float32
                # e^cum Q S0 and W S0 in one product
                both = _dot(jnp.concatenate([x["qe"][part], x["w"][part]],
                                            axis=0), s0.astype(op), 1, 0)
                new = (x["u"][part] - both[chunk:]).astype(op)
                reads.append(both[:chunk])
                news.append(new)
                st[:, lanes] = (x["totals"][i] * s0
                                + _dot(x["ke"][part], new, 0, 0))
            o = (jnp.concatenate(reads, axis=0)
                 + _dot(x["attn"].astype(op), jnp.concatenate(news, axis=0),
                        1, 0))
            o_ref[0, rows, lanes] = o.astype(op)


def _bwd_kernel(q_ref, k_ref, v_ref, cum_ref, beta_ref, do_ref, s0_ref,
                tb_ref, dq_ref, dk_ref, dv_ref, dcum_ref, dbeta_ref, dst, *,
                p, chunk, n):
    """One block's backward given the cotangent ``dst`` of the state that
    LEAVES it."""
    _zero_at_first(dst)
    op = q_ref.dtype
    heads = cum_ref.shape[2]
    masks = _masks(n, chunk)
    at_l, _, causal, strict = masks
    cps = n // chunk
    spans = q_ref.shape[1] // n
    row_sum = lambda t: jnp.sum(t, axis=1, keepdims=True)
    # first to last: what every chunk of the block needs whatever enters
    # it, the states that entered the chunks, from the one that entered
    # the block, and what each chunk wrote
    shareds, xs, states, news = {}, {}, {}, {}
    entering = [s0_ref[0, 0, 0, :, j * p:(j + 1) * p] for j in range(heads)]
    for span in range(spans):
        rows = slice(span * n, (span + 1) * n)
        shareds[span] = _shared(q_ref, k_ref, rows)
        systems = _systems(cum_ref, beta_ref, rows, masks, shareds[span])
        for j in range(heads):
            x = xs[span, j] = _span(
                systems[j], tb_ref[0, 0, j, rows, :],
                v_ref[0, rows, j * p:(j + 1) * p], chunk, masks,
                shareds[span])
            for i in range(cps):
                part = slice(i * chunk, (i + 1) * chunk)
                states[span, j, i] = entering[j]
                new = news[span, j, i] = (x["u"][part] - _dot(
                    x["w"][part], entering[j].astype(op), 1, 0)).astype(op)
                if (span, i) != (spans - 1, cps - 1):
                    entering[j] = x["totals"][i] * entering[j] + _dot(
                        x["ke"][part], new, 0, 0)
    # ... and last to first
    for span in range(spans - 1, -1, -1):
        rows = slice(span * n, (span + 1) * n)
        q, k, _, _ = shareds[span]
        q32, k32 = q.astype(F32), k.astype(F32)
        dq = jnp.zeros(q32.shape, F32)
        dk = jnp.zeros(k32.shape, F32)
        dkk = dqk = jnp.zeros((n, n), F32)
        dcols, drows = [], []
        for j in range(heads):
            lanes = slice(j * p, (j + 1) * p)
            x = xs[span, j]
            do = do_ref[0, rows, lanes]
            dnew_in = _dot(x["attn"].astype(op), do, 0, 0)   # attn^T do
            dnews, dstate, dkes = ([None] * cps for _ in range(3))
            at_end = jnp.zeros((n, 1), F32)
            for i in range(cps - 1, -1, -1):
                part = slice(i * chunk, (i + 1) * chunk)
                s0 = states[span, j, i]
                s0b = s0.astype(op)
                ds1 = dst[:, lanes]
                ds1b = ds1.astype(op)
                dnew = (dnew_in[part]
                        + _dot(x["ke"][part], ds1b, 1, 0)).astype(op)
                # [dQe; -dW] = [do; dnew] S0^T
                stacked = jnp.concatenate([do[part], dnew], axis=0)
                dstate[i] = _dot(stacked, s0b, 1, 1)     # (2C, D)
                dkes[i] = _dot(news[span, j, i], ds1b, 1, 1)     # (C, D)
                dnews[i] = dnew
                # total's gradient lands on cum at the chunk's last token
                dtotal = x["totals"][i] * jnp.sum(
                    row_sum(ds1 * s0), axis=0, keepdims=True)
                at_end = jnp.where(at_l[:, :1] == (i + 1) * chunk - 1,
                                   dtotal, at_end)
                dst[:, lanes] = (x["totals"][i] * ds1 + _dot(
                    jnp.concatenate([x["qe"][part], -x["w"][part]], axis=0),
                    stacked, 0, 0))
            new = jnp.concatenate([news[span, j, i] for i in range(cps)],
                                  axis=0)
            dnew = jnp.concatenate(dnews, axis=0)
            dqe = jnp.concatenate([t[:chunk] for t in dstate], axis=0)
            dw = -jnp.concatenate([t[chunk:] for t in dstate], axis=0)
            dke = jnp.concatenate(dkes, axis=0)
            dattn = jnp.where(causal, _dot(do, new, 1, 1), 0.0)
            dvb = _dot(x["tb"], dnew, 0, 0)              # (n, P): T^T dU
            dkb = _dot(x["tb"], dw.astype(op), 0, 0)     # (n, D): T^T dW
            da = -jnp.where(strict, _dot(
                jnp.concatenate([dvb.astype(op), dkb.astype(op)], axis=1),
                jnp.concatenate([x["u"].astype(op), x["w"]], axis=1), 1, 1),
                0.0)
            dv_ref[0, rows, lanes] = (dvb * x["beta"]).astype(op)
            dak = da * x["kd"]
            moved = dak * x["beta"] + dattn * x["attn"]  # d decay (.) decay
            dkk = dkk + da * x["decay"] * x["beta"]
            dqk = dqk + dattn * x["decay"]
            kb_k, ke_k, qe_q = (row_sum(dkb * k32), row_sum(dke * k32),
                                row_sum(dqe * q32))
            to_end = ke_k * x["eend"]
            # what a chunk's tokens gave its end goes to its last one
            ends = jnp.zeros((n, 1), F32)
            for i in range(cps):
                inside = (at_l[:, :1] >= i * chunk) & (
                    at_l[:, :1] < (i + 1) * chunk)
                ends = jnp.where(
                    at_l[:, :1] == (i + 1) * chunk - 1, jnp.sum(
                        jnp.where(inside, to_end, 0.0), axis=0,
                        keepdims=True), ends)
            dcols += [row_sum(moved) + (kb_k * x["beta"] + qe_q) * x["ecum"]
                      - to_end + ends + at_end,
                      row_sum(dak) + row_sum(dvb * x["v"].astype(F32))
                      + kb_k * x["ecum"]]
            drows.append(-jnp.sum(moved, axis=0, keepdims=True))
            dk = dk + dkb * (x["beta"] * x["ecum"]) + dke * x["eend"]
            dq = dq + dqe * x["ecum"]
        dkk, dqk = dkk.astype(op), dqk.astype(op)
        dq_ref[0, rows, :] = (dq + _dot(dqk, k, 1, 0)).astype(op)
        dk_ref[0, rows, :] = (dk + _dot(dkk, k, 1, 0) + _dot(dkk, k, 0, 0)
                              + _dot(dqk, q, 0, 0)).astype(op)
        if heads < 4:
            dcols.append(jnp.zeros((n, 8 - 2 * heads), F32))
        turned = jnp.concatenate(dcols, axis=1).T            # (8, n)
        for j in range(heads):
            dcum_ref[0, 0, j:j + 1, rows] = (turned[2 * j:2 * j + 1]
                                             + drows[j])
            dbeta_ref[0, 0, j:j + 1, rows] = turned[2 * j + 1:2 * j + 2]


def _specs(block, d, p, heads, n, flip):
    """Block specs of one (batch, key head, block) step; ``flip`` is the
    number of blocks when the grid walks them last to first, else 0."""
    at = (lambda z: flip - 1 - z) if flip else (lambda z: z)
    return dict(
        qk=pl.BlockSpec((1, block, d), lambda bi, h, z: (bi, at(z), h)),
        v=pl.BlockSpec((1, block, heads * p),
                       lambda bi, h, z: (bi, at(z), h)),
        head=pl.BlockSpec((1, 1, heads, block),
                          lambda bi, h, z: (bi, h, 0, at(z))),
        state=pl.BlockSpec((1, 1, 1, d, heads * p),
                           lambda bi, h, z: (bi, h, at(z), 0, 0)),
        inverse=pl.BlockSpec((1, 1, heads, block, n),
                             lambda bi, h, z: (bi, h, 0, at(z), 0)))


def _dims(q3, v3, cum4, chunk):
    """(batch, key heads, value heads a key head, tokens, key lanes, value
    lanes, span, block)."""
    bsz, hk, rep, s = cum4.shape
    n = max(chunk, _LANES)
    block = max(_BLOCK, n)
    while s % block:                    # whole spans, a divisor of the row
        block -= n
    return (bsz, hk, rep, s, q3.shape[2] // hk, v3.shape[2] // (hk * rep), n,
            block)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _forward(q3, k3, v3, cum4, beta4, chunk, keep, interpret):
    """o, and with ``keep`` the (B, Hk, S / block, D, R*P) float32 states
    that entered the grid steps and the (B, Hk, R, S, span) inverses."""
    bsz, hk, rep, s, d, p, n, block = _dims(q3, v3, cum4, chunk)
    sp = _specs(block, d, p, rep, n, 0)
    out_specs = [sp["v"]] + [sp["state"], sp["inverse"]] * keep
    out_shape = [jax.ShapeDtypeStruct(v3.shape, v3.dtype)] + [
        jax.ShapeDtypeStruct((bsz, hk, s // block, d, rep * p), F32),
        jax.ShapeDtypeStruct((bsz, hk, rep, s, n), q3.dtype)] * keep
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, chunk=chunk, n=n),
        grid=(bsz, hk, s // block),
        in_specs=[sp["qk"], sp["qk"], sp["v"], sp["head"], sp["head"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((d, rep * p), F32)],
        compiler_params=_PARAMS, name="delta_rule_fwd", interpret=interpret,
    )(q3, k3, v3, cum4, beta4)


@functools.partial(jax.jit, static_argnums=(8, 9))
def _backward(q3, k3, v3, cum4, beta4, do3, s0, tb, chunk, interpret):
    bsz, hk, rep, s, d, p, n, block = _dims(q3, v3, cum4, chunk)
    sp = _specs(block, d, p, rep, n, s // block)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, chunk=chunk, n=n),
        grid=(bsz, hk, s // block),
        in_specs=[sp["qk"], sp["qk"], sp["v"], sp["head"], sp["head"],
                  sp["v"], sp["state"], sp["inverse"]],
        out_specs=[sp["qk"], sp["qk"], sp["v"], sp["head"], sp["head"]],
        out_shape=[like(q3), like(k3), like(v3), like(cum4), like(beta4)],
        scratch_shapes=[pltpu.VMEM((d, rep * p), F32)],
        compiler_params=_PARAMS, name="delta_rule_bwd", interpret=interpret,
    )(q3, k3, v3, cum4, beta4, do3, s0, tb)


def _operands(q, k, v, g, beta, chunk):
    """The kernels' operands: q, k, v as (B, S, width), and a head and
    token beta and the log-decay from each chunk's start to each of its
    positions, inclusive."""
    bsz, s, hk, _ = q.shape
    hv = v.shape[2]
    cum = jnp.cumsum(g.reshape(bsz, s // chunk, chunk, hv),
                     axis=2).reshape(bsz, s, hv)
    return (q.reshape(bsz, s, -1), k.reshape(bsz, s, -1),
            v.reshape(bsz, s, -1), _per_head(cum, hk), _per_head(beta, hk))


def _run_forward(args, chunk, keep, interpret):
    v = args[2]
    with x64_scope(False):
        o, *kept = _forward(*_operands(*args, chunk), chunk, keep, interpret)
    return (o.reshape(v.shape), *kept)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def delta_rule(q, k, v, g, beta, chunk, interpret):
    """q, k (B, S, Hk, D), v (B, S, Hv, P), g, beta (B, S, Hv) float32, S a
    multiple of ``max(chunk, 128)`` -> o (B, S, Hv, P) in v's type."""
    return _run_forward((q, k, v, g, beta), chunk, False, interpret)[0]


def _delta_rule_fwd(*args):
    *operands, chunk, interpret = args
    o, states, inverses = _run_forward(operands, chunk, True, interpret)
    return o, (*operands, states, inverses)


def _delta_rule_bwd(chunk, interpret, residuals, do):
    q, k, v, g, beta, states, inverses = residuals
    bsz, s, hv, _ = v.shape
    with x64_scope(False):
        q3, k3, v3, cum4, beta4 = _operands(q, k, v, g, beta, chunk)
        dq, dk, dv, dcum, dbeta = _backward(
            q3, k3, v3, cum4, beta4, do.astype(v.dtype).reshape(v3.shape),
            states, inverses, chunk, interpret)
        per_token = lambda t: t.reshape(bsz, hv, s).transpose(0, 2, 1)
        # cum_l = sum of g over the chunk's positions up to l
        dg = jax.lax.cumsum(per_token(dcum).reshape(
            bsz, s // chunk, chunk, hv), axis=2, reverse=True)
        return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
                dg.reshape(bsz, s, hv), per_token(dbeta))


# under ``jax.checkpoint`` (a recomputed layer) the first forward keeps no
# residual, and a ``pallas_call`` has no rule that drops an unused output:
# ``optimize_remat`` runs the primal there, which writes o alone
delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd, optimize_remat=True)
