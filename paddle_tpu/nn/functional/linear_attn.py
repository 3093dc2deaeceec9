"""Linear-attention functionals: the gated delta rule (Yang, Kautz &
Hatamizadeh 2024, "Gated Delta Networks", arXiv:2412.06464), the recurrent
layer of a Gated DeltaNet mixer.

Per value head, with a float32 state ``S`` (d_k, d_v), a log-decay ``g_t <=
0`` and a write strength ``beta_t`` in (0, 1)::

    S   <- exp(g_t) S
    u_t  = beta_t (v_t - S^T k_t)        # what the state gets wrong of v_t
    S   <- S + k_t u_t^T                 # a rank-one CORRECTION
    o_t  = S^T q_t

Unlike a Mamba-2 scan (``ssm.py``), what a token writes depends on the
state it meets, so a chunk is no masked product alone: inside a chunk of C
tokens the corrections solve a unit lower-triangular system, ``(I +
tril_(beta K K^T (.) decay)) U = beta (V - decayed K S_in)``.

* :func:`gated_delta_rule_raw` — the chunked form a training step runs, by
  one of two implementations chosen by what the code can observe
  (``kernels.delta_rule.supported``).  On a TPU, or inside
  ``flash_attention.interpret_scope()``, with key and value heads of whole
  128-lane tiles: two Pallas kernels under a ``custom_vjp``
  (``kernels/delta_rule.py``), a chunk's (C, C) system made, inverted and
  used in VMEM, the state carried down the grid, q, k, v and o read and
  written where the projections keep them.  Everywhere else (a CPU, the
  rehearsal sizes of 16 lanes a head): plain ``jnp`` contractions
  differentiated by JAX, a ``jax.checkpoint`` of their operands — the
  triangular inverse by (block) forward substitution with a backward of
  its own (``dA = -T^T dT T^T``), five (C, C) products a value head, then
  the state carried from chunk to chunk by a ``lax.scan``, key heads in
  groups past a budget of kept states;
* :func:`gated_delta_rule_recurrence_raw` — the equations above a token at
  a time, for tests;
* :func:`l2_normalize_raw` — the per-head normalisation of q and k in front
  of the rule.

Decays, write strengths, the inverse and the carried state are float32
whatever the activations' type; the matrix products take their operands in
the activations' type and accumulate in float32.  ``linear_attn.scan_calls
{path}`` says which implementation was traced (``pallas`` or
``chunked_jnp``).  Raw functions over jax arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...kernels import delta_rule as _kernel
from ...kernels import flash_attention as _fa

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: the side of the diagonal blocks inverted a row at a time
_SUBSTITUTION_BLOCK = 16
#: the most a backward may keep of the states that entered its chunks
#: (float32, one a chunk a value head): past it the key heads go in groups
_STATE_HISTORY_BYTES = 256 * 2 ** 20


def note_scan_call(path: str) -> None:
    """Drive ``linear_attn.scan_calls{path}`` at trace time: one inc per
    delta rule traced, as ``ssm.scan_calls``."""
    try:
        from ...observability import registry as _reg
        _reg.counter("linear_attn.scan_calls",
                     ("path",)).labels(path=path).inc()
    except Exception:
        pass


def l2_normalize_raw(x, epsilon=1e-6, scale=1.0):
    """``scale * x / sqrt(sum x^2 + epsilon)`` over the last axis, in
    float32, rounded once to x's type."""
    xf = x.astype(F32)
    return (xf * (scale * jax.lax.rsqrt(jnp.sum(
        jnp.square(xf), axis=-1, keepdims=True) + epsilon))).astype(x.dtype)


# -- (I + A)^-1 of a strictly lower-triangular A --------------------------------

def _forward_substitution(a):
    """``(I + a)^-1 - I`` of strictly lower-triangular ``a`` (..., n, n), a
    row at a time: row i is ``-a_i - sum_{j<i} a_ij row_j``.  The batch
    goes last, so a row is whole lanes whatever n is."""
    n = a.shape[-1]
    neg = -jnp.moveaxis(a.reshape((-1, n, n)), 0, -1)       # (n, n, M)
    rows = [neg[0]]                                         # zeros
    for i in range(1, n):
        rows.append(neg[i] + jnp.sum(
            neg[i, :i, None, :] * jnp.stack(rows), axis=0))
    return jnp.moveaxis(jnp.stack(rows), -1, 0).reshape(a.shape)


def _inverse(a):
    n = a.shape[-1]
    if n <= _SUBSTITUTION_BLOCK or n % 2:
        return _forward_substitution(a) + jnp.eye(n, dtype=a.dtype)
    # block forward substitution: [[T11, 0], [-T22 A21 T11, T22]]
    h = n // 2
    t11, t22 = _inverse(a[..., :h, :h]), _inverse(a[..., h:, h:])
    t21 = -jnp.matmul(jnp.matmul(t22, a[..., h:, :h], precision=HIGHEST),
                      t11, precision=HIGHEST)
    return jnp.concatenate([
        jnp.concatenate([t11, jnp.zeros_like(t21)], axis=-1),
        jnp.concatenate([t21, t22], axis=-1)], axis=-2)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular float32 ``a`` (..., n,
    n), by forward substitution (in blocks of 16 rows, merged by products):
    exact for any such ``a``, where a truncated series of powers is not."""
    return _inverse(a)


def _inverse_fwd(a):
    t = _inverse(a)
    return t, t


def _inverse_bwd(t, grad):
    tt = jnp.swapaxes(t, -1, -2)
    d = -jnp.matmul(jnp.matmul(tt, grad, precision=HIGHEST), tt,
                    precision=HIGHEST)
    n = t.shape[-1]
    return (jnp.where(jnp.tril(jnp.ones((n, n), bool), -1), d, 0.0),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# -- the chunked rule -----------------------------------------------------------

def _head_major(a, chunk, heads):
    """(B, S, heads..., X) -> (B, heads..., S / chunk, chunk, X): the heads
    lead every product as batch axes (``heads`` says how many axes they
    are)."""
    a = a.reshape((a.shape[0], a.shape[1] // chunk, chunk) + a.shape[2:])
    return jnp.moveaxis(a, (1, 2), (1 + heads, 2 + heads))


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _delta_chunks(q, k, v, g, beta, chunk):
    """The rule proper, on a row whose length is a multiple of ``chunk``.
    q, k (B, S, G, D): key heads; v (B, S, G, R, P): value heads as (key
    head, value head of it); g, beta (B, S, G, R) float32."""
    op = v.dtype                        # the matrix products' operand type
    qc, kc = _head_major(q, chunk, 1), _head_major(k, chunk, 1)  # (B,G,N,C,D)
    vc = _head_major(v, chunk, 2)                           # (B,G,R,N,C,P)
    gc = _head_major(g[..., None], chunk, 2)[..., 0]        # (B,G,R,N,C)
    bc = _head_major(beta[..., None], chunk, 2)[..., 0]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    # log-decay from a chunk's start to each of its positions, inclusive
    cum = jnp.cumsum(gc, axis=-1)
    decay = jnp.exp(jnp.where(
        lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    # -- the corrections of a chunk, given a zero entering state:
    #    (I + tril_(beta_l k_l.k_s e^{cum_l-cum_s})) U = beta V
    kk = jnp.einsum("bgnld,bgnsd->bgnls", kc, kc,
                    preferred_element_type=F32)             # (B,G,N,C,C)
    t = unit_lower_inverse(jnp.where(
        strict, kk[:, :, None] * decay * bc[..., None], 0.0)
    ).astype(op)                                            # (B,G,R,N,C,C)
    k32 = kc.astype(F32)[:, :, None]                        # (B,G,1,N,C,D)
    u = jnp.einsum("bgrnls,bgrnsp->bgrnlp", t,
                   (vc.astype(F32) * bc[..., None]).astype(op),
                   preferred_element_type=F32)
    # ... and what an entering state S takes off them: U - W S
    w = jnp.einsum("bgrnls,bgrnsd->bgrnld", t,
                   (k32 * (bc * jnp.exp(cum))[..., None]).astype(op),
                   preferred_element_type=F32).astype(op)
    # -- reading: o_l = e^{cum_l} q_l.S_in + sum_{s<=l} q_l.k_s e^{..} u_s
    qk = jnp.einsum("bgnld,bgnsd->bgnls", qc, kc,
                    preferred_element_type=F32)
    attn = jnp.where(lower, qk[:, :, None] * decay, 0.0).astype(op)
    q_in = (qc.astype(F32)[:, :, None]
            * jnp.exp(cum)[..., None]).astype(op)           # (B,G,R,N,C,D)
    # -- the state at a chunk's end: e^{cum_end} S_in + sum_s e^{..} k_s u_s
    k_end = (k32 * jnp.exp(cum[..., -1:] - cum)[..., None]).astype(op)
    total = jnp.exp(cum[..., -1])                           # (B,G,R,N)

    def one_chunk(state, inputs):               # state (B,G,R,D,P) float32
        u_i, w_i, attn_i, q_i, k_i, total_i = inputs
        s_op = state.astype(op)
        new = (u_i - jnp.einsum("bgrld,bgrdp->bgrlp", w_i, s_op,
                                preferred_element_type=F32)).astype(op)
        out = (jnp.einsum("bgrld,bgrdp->bgrlp", q_i, s_op,
                          preferred_element_type=F32)
               + jnp.einsum("bgrls,bgrsp->bgrlp", attn_i, new,
                            preferred_element_type=F32))
        state = (state * total_i[..., None, None]
                 + jnp.einsum("bgrsd,bgrsp->bgrdp", k_i, new,
                              preferred_element_type=F32))
        return state, out.astype(op)

    bsz, groups, rep, _, _, p = vc.shape
    _, out = jax.lax.scan(
        one_chunk, jnp.zeros((bsz, groups, rep, q.shape[-1], p), F32),
        tuple(jnp.moveaxis(a, 3, 0) for a in (u, w, attn, q_in, k_end,
                                              total)))
    # (N, B, G, R, C, P) -> (B, S, G, R, P)
    return jnp.transpose(out, (1, 0, 4, 2, 3, 5)).reshape(v.shape)


def _head_groups(state_history_bytes: int, key_heads: int) -> int:
    """The fewest groups of key heads, a divisor of their number, that
    keep a group's history of chunk states under the budget."""
    for groups in range(1, key_heads + 1):
        if key_heads % groups == 0 and (
                state_history_bytes <= groups * _STATE_HISTORY_BYTES):
            return groups
    return key_heads


def gated_delta_rule_raw(q, k, v, g, beta, chunk=64):
    """The gated delta rule over one row, a chunk at a time.

    q, k (B, S, Hk, D): normalised, q scaled; v (B, S, Hv, P), value head h
    reads key head ``h // (Hv / Hk)``; g (B, S, Hv) float32 log-decays
    (<= 0); beta (B, S, Hv) in (0, 1).  Returns o (B, S, Hv, P) in v's
    type.  A length that is no multiple of ``chunk`` is padded with tokens
    that neither decay the state (g = 0) nor write to it (beta = 0).  Where
    ``kernels.delta_rule.supported`` says so the Pallas kernels run; else
    the ``jnp`` chunks, in which a row whose chunks' states would pass
    ``_STATE_HISTORY_BYTES`` in the backward (a layer of 16k tokens and 32
    value heads keeps 512 MiB of them) goes through in groups of key heads,
    one after the other."""
    bsz, s, hv, p = v.shape
    hk = k.shape[2]
    rep = hv // hk
    interpret = bool(_fa._INTERPRET)
    kernels = _kernel.supported(chunk, rep, q.shape[-1], p, interpret)
    # the kernels take whole spans of 128 tokens
    pad = -s % (max(chunk, 128) if kernels else chunk)
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (t.ndim - 2)) for t in (q, k, v, g, beta))
    g, beta = g.astype(F32), beta.astype(F32)
    if kernels:
        note_scan_call("pallas")
        return _kernel.delta_rule(q, k, v, g, beta, chunk, interpret)[:, :s]
    note_scan_call("chunked_jnp")
    operands = (q, k, v.reshape(bsz, s + pad, hk, rep, p),
                g.reshape(bsz, s + pad, hk, rep),
                beta.reshape(bsz, s + pad, hk, rep))
    groups = _head_groups(bsz * (s + pad) // chunk * hv * q.shape[-1] * p
                          * 4, hk)
    if groups == 1:
        out = _delta_chunks(*operands, chunk)
    else:
        # heads do not mix: a group of key heads (with their value heads)
        # after the other, so that a backward holds one group's arrays
        split = lambda t: jnp.moveaxis(t.reshape(
            t.shape[:2] + (groups, hk // groups) + t.shape[3:]), 2, 0)
        out = jax.lax.map(lambda group: _delta_chunks(*group, chunk),
                          tuple(split(t) for t in operands))
        out = jnp.moveaxis(out, 0, 2)
    return out.reshape(bsz, s + pad, hv, p)[:, :s]


def gated_delta_rule_recurrence_raw(q, k, v, g, beta):
    """:func:`gated_delta_rule_raw`'s equations a token at a time, in
    float32: the definition the chunked form is tested against."""
    bsz, s, hv, p = v.shape
    rep = hv // k.shape[2]
    expand = lambda t: jnp.repeat(t.astype(F32), rep, axis=2)
    qs, ks = expand(q), expand(k)

    def step(state, inputs):                    # state (B, Hv, D, P)
        q_t, k_t, v_t, g_t, beta_t = inputs
        state = jnp.exp(g_t)[..., None, None] * state
        u_t = beta_t[..., None] * (
            v_t - jnp.einsum("bhdp,bhd->bhp", state, k_t, precision=HIGHEST))
        state = state + k_t[..., :, None] * u_t[..., None, :]
        return state, jnp.einsum("bhdp,bhd->bhp", state, q_t,
                                 precision=HIGHEST)

    _, out = jax.lax.scan(
        step, jnp.zeros((bsz, hv, q.shape[-1], p), F32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (
            qs, ks, v.astype(F32), g.astype(F32), beta.astype(F32))))
    return jnp.moveaxis(out, 0, 1)
