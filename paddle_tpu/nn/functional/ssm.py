"""State-space functionals: what a Mamba-2 mixer is made of (Dao & Gu 2024,
"Transformers are SSMs", the state-space duality algorithm).

* :func:`causal_conv1d_raw` — the causal depthwise convolution in front of
  the scan;
* :func:`conv_split_raw` — all that stands between a mixer's fused input
  projection and its scan: that convolution over some of the projection's
  columns, SiLU, the split into the scan's operands and a per-head L2
  normalisation of the parts that ask.  On a TPU, for columns in whole
  128-lane tiles, ``kernels/causal_conv.py``: a Pallas kernel each way
  that reads the projection's buffer in place and writes each part where
  the scan's kernels read it.  Everywhere else the ``jnp`` functions, and
  ``ssm.conv_calls{path}`` says which was traced;
* :func:`ssd_scan_raw` — ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = C_t . S_t + D x_t``, computed a chunk at a time: inside a chunk as
  masked matrix products (the "dual" quadratic form), between chunks as a
  recurrence on the chunk states.  On a TPU, for a chunk, a state and a
  group's heads in whole 128-lane tiles, that is ``kernels/ssd_scan.py``: a
  Pallas kernel each way under a ``custom_vjp`` (the (chunk, chunk) decays
  stay in VMEM, the state is carried down the grid).  Everywhere else (a
  CPU, the tiny test configuration) plain ``jnp`` contractions,
  differentiated by JAX, a ``jax.checkpoint`` of their operands.  Both make
  the decay matrices again in the backward, as the published kernels do,
  and ``ssm.scan_calls{path}`` says which was traced;
* :func:`ssd_recurrence_raw` — the same equations a token at a time, for
  tests;
* :func:`gated_group_rms_norm_raw` — ``GroupRMSNorm(y * silu(z)) * w``.

Decays, step sizes and the carried state are float32 whatever the
activations' type; the matrix products take their operands in the
activations' type and accumulate in float32.  Raw functions over jax arrays:
a model calls them through ``core.dispatch.call``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...kernels import causal_conv as _conv_kernel
from ...kernels import flash_attention as _fa
from ...kernels import ssd_scan as _kernel

F32 = jnp.float32


def _count(name: str, path: str) -> None:
    try:
        from ...observability import registry as _reg
        _reg.counter(name, ("path",)).labels(path=path).inc()
    except Exception:
        pass


def note_scan_call(path: str) -> None:
    """Drive ``ssm.scan_calls{path}`` at trace time: one inc per scan
    traced (a compile-once program contributes once a trace, as the
    ``flash.*`` counters do)."""
    _count("ssm.scan_calls", path)


def note_conv_call(path: str) -> None:
    """Drive ``ssm.conv_calls{path}`` at trace time: one inc per
    :func:`conv_split_raw` traced, ``pallas`` or ``jnp``."""
    _count("ssm.conv_calls", path)


def causal_conv1d_raw(x, weight, bias=None, silu=False):
    """x (b, s, c), weight (k, c), bias (c,) -> (b, s, c):
    ``y_t = sum_j weight[j] * x_{t-(k-1)+j} + bias``, zeros before the
    row's start, then ``silu`` if asked.  Float32 throughout, rounded once
    to the input's type."""
    k, s = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(F32)
    out = sum(w[j] * padded[:, j:j + s].astype(F32) for j in range(k))
    if bias is not None:
        out = out + bias.astype(F32)
    if silu:
        out = jax.nn.silu(out)
    return out.astype(x.dtype)


def _conv_split_jnp(proj, offset, parts, weight, bias, silu):
    """:func:`conv_split_raw` by ``causal_conv1d_raw`` on a slice, slices
    and ``l2_normalize_raw``."""
    from .linear_attn import l2_normalize_raw
    bsz, s, _ = proj.shape
    x = causal_conv1d_raw(proj[..., offset:offset + weight.shape[1]],
                          weight, bias, silu)
    outs, at = [], 0
    for width, head, scale in parts:
        part = x[..., at:at + width]
        if head:
            part = l2_normalize_raw(
                part.reshape(bsz, s, width // head, head),
                scale=scale).reshape(bsz, s, width)
        outs.append(part)
        at += width
    return tuple(outs)


def conv_split_raw(proj, offset, parts, weight, bias=None, silu=False):
    """proj (b, s, w): a fused projection as its GEMM leaves it; weight (k,
    c), bias (c,) over the ``c`` columns from ``offset`` on, ``c`` the sum
    of the parts' widths.  ``parts``: ``(width, head_dim or None, scale)``
    each, in the columns' order.  Returns a flat (b, s, width) array a
    part: ``causal_conv1d_raw`` of those columns, cut into the parts, a
    part with a ``head_dim`` L2-normalised over each head's lanes and
    multiplied by ``scale`` (``linear_attn.l2_normalize_raw``).  Where
    ``kernels.causal_conv.supported`` says so the Pallas kernels run, which
    read ``proj`` in place; else the ``jnp`` functions."""
    parts = tuple((int(w), h and int(h), float(scale))
                  for w, h, scale in parts)
    interpret = bool(_fa._INTERPRET)
    if _conv_kernel.supported(proj.shape[1], proj.shape[2], offset, parts,
                              weight.shape[0], interpret):
        note_conv_call("pallas")
        return _conv_kernel.conv_split(proj, weight, bias, offset, parts,
                                       silu, interpret)
    note_conv_call("jnp")
    return _conv_split_jnp(proj, offset, parts, weight, bias, silu)


def _chunked(a, chunk):
    """(b, s, ...) -> (b, s / chunk, chunk, ...)."""
    return a.reshape((a.shape[0], a.shape[1] // chunk, chunk) + a.shape[2:])


@functools.partial(jax.checkpoint, static_argnums=(6,))
def _ssd_chunks(x, dt, a, b, c, d, chunk):
    """The scan proper, on a row whose length is a multiple of ``chunk``.
    x (B, S, G, R, P): heads as (group, head in group); dt (B, S, G, R)
    f32; a, d (G, R) f32; b, c (B, S, G, N)."""
    xc, dtc, bc, cc = (_chunked(t, chunk) for t in (x, dt, b, c))
    op = x.dtype                       # the matrix products' operand type
    # log-decay from a chunk's start to each of its positions, inclusive
    cum = jnp.cumsum(dtc * a, axis=2)                       # (B,C,L,G,R)
    cum_t = jnp.moveaxis(cum, 2, -1)                        # (B,C,G,R,L)
    # -- inside a chunk: y_l += sum_{s<=l} (C_l.B_s) e^{cum_l-cum_s} dt_s x_s
    scores = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc,
                        preferred_element_type=F32)         # (B,C,G,L,L)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        causal, cum_t[..., :, None] - cum_t[..., None, :], -jnp.inf))
    weight = (scores[:, :, :, None] * decay
              * jnp.moveaxis(dtc, 2, -1)[..., None, :])     # (B,C,G,R,L,L)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", weight.astype(op), xc,
                   preferred_element_type=F32)
    # -- a chunk's own state at its end: sum_s e^{cum_end-cum_s} dt_s x_s(x)B_s
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dtc            # (B,C,L,G,R)
    states = jnp.einsum("bcsgrp,bcsgn->bcgrpn",
                        (xc.astype(F32) * to_end[..., None]).astype(op), bc,
                        preferred_element_type=F32)         # (B,C,G,R,P,N)
    # -- between chunks: the state that enters chunk z is
    #    sum_{c<z} e^{sum of the whole-chunk log-decays of c+1..z-1} states_c
    total = jnp.moveaxis(cum[:, :, -1], 1, -1)              # (B,G,R,C)
    n = total.shape[-1]
    below = jnp.tril(jnp.ones((n, n), bool), -1)            # [j, c]: j > c
    # seg[z, c] = sum_{c<j<z} total_j, by a cumulative sum down the rows of
    # the strictly-lower triangle (no difference of large sums)
    seg = jnp.cumsum(jnp.where(below, total[..., :, None], 0.0), axis=-2)
    seg = seg - jnp.where(below, total[..., :, None], 0.0)   # exclusive of z
    carry = jnp.exp(jnp.where(below, seg, -jnp.inf))        # (B,G,R,Z,C)
    entering = jnp.einsum("bgrzc,bcgrpn->bzgrpn", carry, states,
                          precision=jax.lax.Precision.HIGHEST)
    y = y + jnp.einsum("bclgn,bcgrpn->bclgrp", cc, entering.astype(op),
                       preferred_element_type=F32) * jnp.exp(cum)[..., None]
    y = y + d[:, :, None] * xc.astype(F32)
    return y.reshape(x.shape).astype(op)


def ssd_scan_raw(x, dt, a, b, c, d, chunk):
    """The Mamba-2 recurrence over one row, a chunk at a time.

    x (B, S, H, P) heads of P; dt (B, S, H) float32, after its softplus;
    a (H,) float32, negative; b, c (B, S, G, N), head h reads group
    h // (H / G); d (H,).  Returns y (B, S, H, P) in x's type.  A length
    that is no multiple of ``chunk`` is padded with steps of size zero,
    which neither decay the state nor add to it."""
    bsz, s, h, p = x.shape
    g = b.shape[2]
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                               (t.ndim - 2)) for t in (x, dt, b, c))
    dt, a, d = dt.astype(F32), a.astype(F32), d.astype(F32)
    interpret = bool(_fa._INTERPRET)
    if _kernel.supported(chunk, h // g, p, b.shape[3], interpret):
        note_scan_call("pallas")
        y = _kernel.ssd_scan(x, dt, a, b, c, d, chunk, interpret)
    else:
        note_scan_call("chunked_jnp")
        y = _ssd_chunks(x.reshape(bsz, s + pad, g, h // g, p),
                        dt.reshape(bsz, s + pad, g, h // g),
                        a.reshape(g, h // g), b, c, d.reshape(g, h // g),
                        chunk).reshape(bsz, s + pad, h, p)
    return y[:, :s]


def ssd_recurrence_raw(x, dt, a, b, c, d):
    """:func:`ssd_scan_raw`'s equations a token at a time, in float32: the
    definition the chunked form is tested against."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    expand = lambda t: jnp.repeat(t.astype(F32), h // g, axis=2)
    xs, bs, cs = x.astype(F32), expand(b), expand(c)
    dts = dt.astype(F32)

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    _, ys = jax.lax.scan(step, jnp.zeros((bsz, h, p, n), F32),
                         tuple(jnp.moveaxis(t, 1, 0)
                               for t in (xs, dts, bs, cs)))
    return jnp.moveaxis(ys, 0, 1) + d[:, None] * xs


def gated_group_rms_norm_raw(y, z, weight, groups, epsilon):
    """``GroupRMSNorm_groups(y * silu(z)) * weight`` over the last axis, in
    float32, y's type out."""
    gated = y.astype(F32) * jax.nn.silu(z.astype(F32))
    grouped = gated.reshape(gated.shape[:-1] + (groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + epsilon)
    return (grouped.reshape(gated.shape) * weight.astype(F32)).astype(y.dtype)
