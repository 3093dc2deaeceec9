"""Decomposed collective matmuls for the tensor-parallel axis (ISSUE 20).

GSPMD lowers the Megatron pairs to *monolithic* collectives: the row
matmul's partial sums meet in one all-reduce, the LM head either
all-gathers the vocab-sharded table or all-gathers per-shard logits, and
the fused-qkv slice reshard becomes an all-to-all/all-gather per layer.
Each of those serializes the full transfer before (or after) the full
matmul.  This module rewrites each site as a **ppermute ring under
``shard_map``** on the existing ``('mp',)`` mesh so every step moves one
shard-sized block while the previous block's partial matmul is still on
the MXU — the classic collective-matmul overlap:

* ``row_parallel_matmul``  — matmul→all-reduce becomes partial-accumulate
  + chunked permute (matmul→reduce-scatter ring) followed by a ring
  all-gather.  At step ``t`` device ``d`` computes its partial of output
  block ``(d+t+1) mod n`` and adds the accumulator that just arrived from
  device ``d+1``; after ``n`` steps block ``d`` is fully reduced in place.
* ``column_parallel_matmul`` — forward is collective-free (identity);
  the ``custom_vjp`` backward runs the *transposed* collective (dx's
  matmul→all-reduce) through the same ring.
* ``lm_head_matmul``       — all-gather→matmul becomes a rotate-weights
  ring: each step matmuls the resident vocab shard into its slice of the
  logits while the next shard is in flight.
* ``qkv_heads``            — the fused-qkv reshard (PR 11's named
  follow-up): the column-sharded ``(B,S,3H/tp)`` projection output is
  re-dealt to the head-sharded q/k/v layout with three single-hop
  ppermutes (a bijection whenever ``gcd(3, tp) == 1`` — every
  power-of-two tp) instead of GSPMD's all-to-all + all-gather.

The switch is three-level — per-call arg > :func:`overlap_scope` >
``PADDLE_TPU_MP_OVERLAP`` env — and is read at TRACE time, so a jitted
program's lowering is decided once: off ⇒ the wrappers return ``None``
and callers keep today's GSPMD lowering bit-for-bit.

Numerics: the ring performs the same shard-local partial matmuls as
GSPMD's partitioned dot, summed in a fixed ring order.  For ``n = 2``
the two-term f32 sum is commutative, so greedy decode is bit-identical
to the monolithic lowering; for ``n > 2`` the reduction order differs
(associativity) and parity is tight-tolerance — the same caveat GSPMD
itself carries across all-reduce implementations.

Chunking: each ring block can be split into ``chunks`` column sub-blocks
permuted independently (more, smaller transfers to hide behind shorter
matmuls) — the knob the ``mp_overlap`` autotune family times on chip.
All bodies run with ``check_vma=False``: ppermute results are not
provably replicated to the varying-axes checker even when they are by
construction.
"""
from __future__ import annotations

import contextlib
import os
import threading

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from . import mesh as _mesh

MP_AXIS = "mp"
ENV_FLAG = "PADDLE_TPU_MP_OVERLAP"

_tls = threading.local()


# ---------------------------------------------------------------------------
# the overlap switch: per-call arg > scope > env, resolved at trace time
# ---------------------------------------------------------------------------

def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def env_enabled() -> bool:
    return os.environ.get(ENV_FLAG, "").lower() in ("1", "true", "yes", "on")


@contextlib.contextmanager
def overlap_scope(enabled=True, chunks=None):
    """Pin the overlap switch (and optionally the ring chunk count) for
    everything traced inside — the serving engine wraps its entry traces
    in this so an engine built with ``overlap_comm=False`` stays
    monolithic even under ``PADDLE_TPU_MP_OVERLAP=1``."""
    _stack().append((bool(enabled), chunks))
    try:
        yield
    finally:
        _stack().pop()


def enabled(arg=None) -> bool:
    """Resolve the three-level switch: explicit arg > innermost scope >
    env.  ``None`` means "inherit"."""
    if arg is not None:
        return bool(arg)
    st = _stack()
    if st:
        return st[-1][0]
    return env_enabled()


def scope_chunks():
    st = _stack()
    return st[-1][1] if st else None


def active(arg=None, axis=MP_AXIS):
    """``(mesh, n)`` when an overlapped island should be built at this
    trace point: switch on AND the ambient mesh declares ``axis`` with
    size > 1.  ``None`` ⇒ caller keeps the GSPMD lowering."""
    if not enabled(arg):
        return None
    try:
        mesh = _mesh.get_mesh()
    except Exception:
        return None
    if mesh is None or axis not in getattr(mesh, "axis_names", ()):
        return None
    n = int(mesh.shape[axis])
    if n < 2:
        return None
    return mesh, n


# -- trace-time viability checks (callers branch BEFORE building the op,
# so the off/non-viable path is byte-identical to today's lowering) ---------

def row_viable(k_dim, arg=None):
    """Row matmul: the sharded contraction dim must split over the mesh."""
    act = active(arg)
    return act is not None and int(k_dim) % act[1] == 0


def col_viable(k_dim, n_dim, arg=None):
    """Column matmul: sharded output dim splits; the backward ring also
    blocks the contraction dim over the mesh."""
    act = active(arg)
    return (act is not None and int(n_dim) % act[1] == 0
            and int(k_dim) % act[1] == 0)


def lm_viable(v_dim, arg=None):
    act = active(arg)
    return act is not None and int(v_dim) % act[1] == 0


def qkv_viable(num_heads, head_dim, arg=None):
    """The 3-ppermute re-deal needs gcd(3, tp) == 1 and head-aligned
    shards (``num_heads % tp == 0`` — the engine's own tp precondition)."""
    act = active(arg)
    if act is None:
        return False
    n = act[1]
    return n % 3 != 0 and int(num_heads) % n == 0


def embed_viable(vocab, arg=None):
    act = active(arg)
    return act is not None and int(vocab) % act[1] == 0


# ---------------------------------------------------------------------------
# chunk-count autotuning (the mp_overlap family) + the trace-time counter
# ---------------------------------------------------------------------------

def autotune_key(kind, m, k, n, n_dev, dtype):
    """``kind`` names the ring shape (row / colbwd / lmhead); m/k/n are
    the GLOBAL matmul dims (m = flattened batch rows)."""
    from ..kernels import autotune as at
    return {"kind": str(kind), "m": int(m), "k": int(k), "n": int(n),
            "n_dev": int(n_dev), "dtype": str(jnp.dtype(dtype)),
            "platform": at.platform()}


def _candidates(key):
    """chunks=1 (one permute per ring step — the safe default) first;
    2/4 only when the permuted block splits evenly."""
    n_dev = max(1, int(key.get("n_dev", 1)))
    if key.get("kind") == "lmhead":
        block = int(key.get("n", 0)) // n_dev      # vocab rows per shard
    else:
        block = int(key.get("n", 0)) // n_dev      # output cols per shard
    out = [{"variant": "chunks1", "config": {"chunks": 1}}]
    for c in (2, 4):
        if block > 0 and block % c == 0:
            out.append({"variant": "chunks%d" % c, "config": {"chunks": c}})
    return out


def _runner(cand, key):
    """Time the row ring at the key's shape on the first n_dev local
    devices (chip sessions tune the real transfer/compute ratio; the CPU
    fallback still exercises the code path)."""
    n_dev = int(key["n_dev"])
    devs = jax.devices()
    if len(devs) < n_dev:
        raise RuntimeError("mp_overlap needs %d devices, have %d"
                           % (n_dev, len(devs)))
    from jax.sharding import Mesh
    import numpy as np
    mesh = Mesh(np.asarray(devs[:n_dev]), (MP_AXIS,))
    dtype = jnp.dtype(key["dtype"])
    m, k, n = int(key["m"]), int(key["k"]), int(key["n"])
    chunks = int(cand["config"]["chunks"])
    x = jnp.ones((m, k), dtype)
    w = jnp.ones((k, n), dtype)

    def body(x_l, w_l):
        blk = _ring_mm_rs(x_l, w_l, MP_AXIS, n_dev, chunks)
        return _ring_ag(blk, MP_AXIS, n_dev, chunks)

    fn = jax.jit(shard_map(body, mesh=mesh,
                           in_specs=(P(None, MP_AXIS), P(MP_AXIS, None)),
                           out_specs=P(None, None), check_vma=False))
    fn(x, w).block_until_ready()   # compile outside the timed region

    def run():
        fn(x, w).block_until_ready()
    return run


def _register():
    from ..kernels import autotune as at
    # traceable stays None: the ring is an XLA-level schedule, not a
    # Pallas kernel — the TPU504 VMEM estimator has nothing to price and
    # the pallas/ trace tier must not grow per-chunk twins (the serving
    # tier registers the overlapped PROGRAMS instead)
    at.register_family("mp_overlap", _candidates, runner=_runner,
                       traceable=None)


_register()


def _resolve_chunks(kind, m, k, n, n_dev, dtype, block):
    """Scope pin > autotune resolve (pin > memo > cache > tune > default
    chunks=1), clamped to a divisor of the permuted block."""
    c = scope_chunks()
    if c is None:
        from ..kernels import autotune as at
        cand = at.resolve("mp_overlap",
                          autotune_key(kind, m, k, n, n_dev, dtype))
        c = cand.get("config", {}).get("chunks", 1)
    c = max(1, int(c))
    while block % c:
        c -= 1
    _note_chunks(c)
    return c


def _note_chunks(chunks):
    """Drive the ``mp.overlap_chunks`` counter at trace time — one inc
    per overlapped island built, valued at its ring chunk count (a
    compile-once program contributes once, matching the compile.count
    discipline)."""
    try:
        from ..observability import registry as _reg
        _reg.counter("mp.overlap_chunks").inc(int(chunks))
    except Exception:
        pass


# ---------------------------------------------------------------------------
# ring primitives (shard_map bodies; *_l arrays are per-device shards)
# ---------------------------------------------------------------------------

def _ring_mm_rs(x_l, w_l, axis, n, chunks):
    """matmul→reduce-scatter ring.  ``x_l (..., K/n)``, ``w_l (K/n, N)``;
    returns this device's fully-reduced output block ``(..., N/n)``.
    Block schedule: at step t device d computes its partial of block
    ``(d+t+1) mod n`` and adds the accumulator that just arrived from
    d+1 (permute direction d→d−1), so the in-flight permute hides behind
    the current partial matmul."""
    idx = lax.axis_index(axis)
    nb = w_l.shape[-1] // n
    sub = nb // chunks
    down = [(s, (s - 1) % n) for s in range(n)]

    def piece(i, j):
        return lax.dynamic_slice_in_dim(w_l, i * nb + j * sub, sub, axis=1)

    accs = [x_l @ piece((idx + 1) % n, j) for j in range(chunks)]
    for t in range(1, n):
        accs = [lax.ppermute(a, axis, down) for a in accs]
        accs = [a + x_l @ piece((idx + t + 1) % n, j)
                for j, a in enumerate(accs)]
    return accs[0] if chunks == 1 else jnp.concatenate(accs, axis=-1)


def _ring_ag(y_blk, axis, n, chunks):
    """Ring all-gather of per-device blocks along the last dim: after t
    permutes (direction d→d+1) the resident block is ``(d−t) mod n``;
    each lands in its slice of the full output."""
    idx = lax.axis_index(axis)
    nb = y_blk.shape[-1]
    sub = nb // chunks
    up = [(s, (s + 1) % n) for s in range(n)]
    out = jnp.zeros(y_blk.shape[:-1] + (nb * n,), y_blk.dtype)
    cur = ([y_blk] if chunks == 1 else
           [lax.dynamic_slice_in_dim(y_blk, j * sub, sub, axis=-1)
            for j in range(chunks)])
    for t in range(n):
        blk = (idx - t) % n
        if t + 1 < n:   # issue the permutes before the update slices so
            nxt = [lax.ppermute(p, axis, up) for p in cur]   # they overlap
        for j, piece in enumerate(cur):
            out = lax.dynamic_update_slice_in_dim(
                out, piece, blk * nb + j * sub, axis=-1)
        if t + 1 < n:
            cur = nxt
    return out


def _ring_lm(x_l, w_l, axis, n, chunks):
    """Rotate-weights all-gather→matmul ring for the LM head.  ``x_l``
    is the full ``(..., H)`` activation, ``w_l (V/n, H)`` the resident
    vocab shard; after t permutes (d→d+1) the resident shard is vocab
    block ``(d−t) mod n``.  Each step matmuls the resident shard into
    its logits slice while the next shard is in flight."""
    idx = lax.axis_index(axis)
    vb = w_l.shape[0]
    sub = vb // chunks
    up = [(s, (s + 1) % n) for s in range(n)]
    out = jnp.zeros(x_l.shape[:-1] + (vb * n,), x_l.dtype)
    cur = ([w_l] if chunks == 1 else
           [lax.dynamic_slice_in_dim(w_l, j * sub, sub, axis=0)
            for j in range(chunks)])
    for t in range(n):
        blk = (idx - t) % n
        if t + 1 < n:
            nxt = [lax.ppermute(p, axis, up) for p in cur]
        for j, piece in enumerate(cur):
            out = lax.dynamic_update_slice_in_dim(
                out, x_l @ piece.T, blk * vb + j * sub, axis=-1)
        if t + 1 < n:
            cur = nxt
    return out


def _batch_spec(ndim, axis_last=None):
    return P(*([None] * (ndim - 1) + [axis_last]))


# ---------------------------------------------------------------------------
# row-parallel matmul: ring RS+AG forward, collective-free backward
# ---------------------------------------------------------------------------

def _row_island(x, w, axis, n, chunks):
    mesh = _mesh.get_mesh()

    def body(x_l, w_l):
        blk = _ring_mm_rs(x_l, w_l, axis, n, chunks)
        return _ring_ag(blk, axis, n, chunks)

    return shard_map(
        body, mesh=mesh,
        in_specs=(_batch_spec(x.ndim, axis), P(axis, None)),
        out_specs=_batch_spec(x.ndim), check_vma=False)(x, w)


from functools import partial  # noqa: E402  (decorators below need it)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _row_matmul(x, w, axis, n, chunks):
    return _row_island(x, w, axis, n, chunks)


def _row_fwd(x, w, axis, n, chunks):
    return _row_island(x, w, axis, n, chunks), (x, w)


def _row_bwd(axis, n, chunks, res, dy):
    # Megatron g/f duality: the row forward's all-reduce transposes to
    # identity — both cotangents are shard-local matmuls, no collective
    x, w = res
    mesh = _mesh.get_mesh()

    def body(x_l, w_l, dy_full):
        dx_l = dy_full @ w_l.T
        dw_l = jnp.einsum("...k,...n->kn", x_l, dy_full)
        return dx_l, dw_l

    dx, dw = shard_map(
        body, mesh=mesh,
        in_specs=(_batch_spec(x.ndim, axis), P(axis, None),
                  _batch_spec(dy.ndim)),
        out_specs=(_batch_spec(x.ndim, axis), P(axis, None)),
        check_vma=False)(x, w, dy)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_row_matmul.defvjp(_row_fwd, _row_bwd)


def row_parallel_matmul(x, w, bias=None, arg=None):
    """Overlapped ``x @ w`` with ``w`` sharded on the contraction dim
    (``P('mp', None)``): GSPMD's matmul→all-reduce becomes the
    partial-accumulate + chunked-permute ring.  Returns ``None`` when
    overlap is off / no mp mesh — caller keeps the monolithic path."""
    act = active(arg)
    if act is None:
        return None
    mesh, n = act
    k, nn = int(w.shape[0]), int(w.shape[1])
    if k % n:
        return None
    m = 1
    for d in x.shape[:-1]:
        m *= int(d)
    chunks = _resolve_chunks("row", m, k, nn, n, x.dtype, max(nn // n, 1))
    if (nn // n) % chunks:
        return None
    out = _row_matmul(x, w, MP_AXIS, n, chunks)
    if bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# column-parallel matmul: local forward, ring backward (transposed
# collective interleaved the same way)
# ---------------------------------------------------------------------------

def _col_island(x, w, axis):
    mesh = _mesh.get_mesh()

    def body(x_full, w_l):
        return x_full @ w_l

    return shard_map(
        body, mesh=mesh,
        in_specs=(_batch_spec(x.ndim), P(None, axis)),
        out_specs=_batch_spec(x.ndim, axis), check_vma=False)(x, w)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _col_matmul(x, w, axis, n, chunks):
    return _col_island(x, w, axis)


def _col_fwd(x, w, axis, n, chunks):
    return _col_island(x, w, axis), (x, w)


def _col_bwd(axis, n, chunks, res, dy):
    # dx = dy @ w.T contracts over the SHARDED output dim — the
    # transposed collective.  Ring it exactly like the row forward:
    # a_l = dy shard (..., N/n), b_l = w_l.T (N/n, K).
    x, w = res
    mesh = _mesh.get_mesh()

    def body(x_full, w_l, dy_l):
        dx_blk = _ring_mm_rs(dy_l, w_l.T, axis, n, chunks)
        dx_l = _ring_ag(dx_blk, axis, n, chunks)
        dw_l = jnp.einsum("...k,...n->kn", x_full, dy_l)
        return dx_l, dw_l

    dx, dw = shard_map(
        body, mesh=mesh,
        in_specs=(_batch_spec(x.ndim), P(None, axis),
                  _batch_spec(dy.ndim, axis)),
        out_specs=(_batch_spec(x.ndim), P(None, axis)),
        check_vma=False)(x, w, dy)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_col_matmul.defvjp(_col_fwd, _col_bwd)


def column_parallel_matmul(x, w, bias=None, arg=None):
    """Overlapped ``x @ w`` with ``w`` sharded on the output dim
    (``P(None, 'mp')``).  The forward is collective-free either way; the
    payoff is the custom_vjp backward, whose dx all-reduce runs through
    the ring.  Output stays mp-sharded on the last dim.  ``None`` ⇒
    overlap off."""
    act = active(arg)
    if act is None:
        return None
    mesh, n = act
    k, nn = int(w.shape[0]), int(w.shape[1])
    if nn % n or k % n:
        return None
    m = 1
    for d in x.shape[:-1]:
        m *= int(d)
    chunks = _resolve_chunks("colbwd", m, nn, k, n, x.dtype,
                             max(k // n, 1))
    if (k // n) % chunks:
        return None
    out = _col_matmul(x, w, MP_AXIS, n, chunks)
    if bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# LM head: rotate-weights all-gather→matmul ring over the vocab shards
# ---------------------------------------------------------------------------

def _lm_island(x, w, axis, n, chunks):
    mesh = _mesh.get_mesh()

    def body(x_full, w_l):
        return _ring_lm(x_full, w_l, axis, n, chunks)

    return shard_map(
        body, mesh=mesh,
        in_specs=(_batch_spec(x.ndim), P(axis, None)),
        out_specs=_batch_spec(x.ndim), check_vma=False)(x, w)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _lm_matmul(x, w, axis, n, chunks):
    return _lm_island(x, w, axis, n, chunks)


def _lm_fwd(x, w, axis, n, chunks):
    return _lm_island(x, w, axis, n, chunks), (x, w)


def _lm_bwd(axis, n, chunks, res, dy):
    # dx contracts over the sharded vocab dim: shard-local partial +
    # psum (an all-reduce — permitted; the monolithic ban is on
    # all-gather).  dw is shard-local.
    x, w = res
    mesh = _mesh.get_mesh()
    vb = int(w.shape[0]) // n

    def body(x_full, w_l, dy_full):
        idx = lax.axis_index(axis)
        dy_l = lax.dynamic_slice_in_dim(dy_full, idx * vb, vb, axis=-1)
        dx = lax.psum(dy_l @ w_l, axis)
        dw_l = jnp.einsum("...v,...h->vh", dy_l, x_full)
        return dx, dw_l

    dx, dw = shard_map(
        body, mesh=mesh,
        in_specs=(_batch_spec(x.ndim), P(axis, None),
                  _batch_spec(dy.ndim)),
        out_specs=(_batch_spec(x.ndim), P(axis, None)),
        check_vma=False)(x, w, dy)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_lm_matmul.defvjp(_lm_fwd, _lm_bwd)


def lm_head_matmul(x, wte, arg=None):
    """Overlapped ``x @ wte.T`` with ``wte (V, H)`` vocab-sharded
    (``P('mp', None)``) — the decode LM head.  Replaces GSPMD's
    monolithic table all-gather with the rotate-weights ring; the full
    ``(..., V)`` logits come back replicated.  ``None`` ⇒ overlap off."""
    act = active(arg)
    if act is None:
        return None
    mesh, n = act
    v, h = int(wte.shape[0]), int(wte.shape[1])
    if v % n:
        return None
    m = 1
    for d in x.shape[:-1]:
        m *= int(d)
    chunks = _resolve_chunks("lmhead", m, h, v, n, x.dtype,
                             max(v // n, 1))
    if (v // n) % chunks:
        return None
    return _lm_matmul(x, wte, MP_AXIS, n, chunks)


# ---------------------------------------------------------------------------
# vocab-parallel embedding: masked local gather + psum (no table gather)
# ---------------------------------------------------------------------------

def vocab_embed(ids, wte, arg=None):
    """Vocab-sharded embedding lookup without materialising the table:
    each device gathers the ids that fall in its shard (zeros elsewhere)
    and the rows meet in one psum — an all-reduce of activation bytes
    instead of GSPMD's all-gather of table bytes.  ``None`` ⇒ overlap
    off."""
    act = active(arg)
    if act is None:
        return None
    mesh, n = act
    v = int(wte.shape[0])
    if v % n:
        return None
    vb = v // n

    def body(ids_full, wte_l):
        idx = lax.axis_index(MP_AXIS)
        local = ids_full.astype(jnp.int32) - idx * vb
        ok = (local >= 0) & (local < vb)
        rows = jnp.take(wte_l, jnp.clip(local, 0, vb - 1), axis=0)
        rows = jnp.where(ok[..., None], rows, jnp.zeros((), rows.dtype))
        return lax.psum(rows, MP_AXIS)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(*([None] * ids.ndim)), P(MP_AXIS, None)),
        out_specs=P(*([None] * (ids.ndim + 1))), check_vma=False)(ids, wte)


# ---------------------------------------------------------------------------
# fused-qkv projection + 3-ppermute head reshard (decode-side consumer)
# ---------------------------------------------------------------------------

def qkv_heads(x, w, b, num_heads, head_dim, arg=None):
    """Fused column qkv projection straight into the head-sharded layout.

    ``x (B,S,E)`` replicated, ``w (E, 3H)`` column-sharded, ``b (3H,)``
    sharded or None → ``(q, k, v)`` each ``(B,S,nh,hd)`` head-sharded
    (``P(None,None,'mp',None)`` — the serving pool's layout).

    The column shard boundary (at 3H/tp) does not align with the q/k/v
    split (at H), so GSPMD reshards with an all-to-all + all-gather per
    layer.  In units of ``Hb = H/tp`` device ``s`` holds global blocks
    ``3s, 3s+1, 3s+2`` while device ``d`` needs blocks ``d, tp+d,
    2tp+d`` — for ``gcd(3, tp) == 1`` (every power-of-two tp) each local
    slot ``l`` maps by the bijection ``s → (3s+l) mod tp``, so three
    single-hop ppermutes re-deal everything; the receiver picks q/k/v
    out of the stacked arrivals as slot ``(tp·j + d) mod 3``.  Falls
    back to ``None`` (GSPMD path) when ``tp % 3 == 0`` or shapes don't
    divide."""
    act = active(arg)
    if act is None:
        return None
    mesh, n = act
    if n % 3 == 0:
        return None
    h = num_heads * head_dim
    if int(w.shape[1]) != 3 * h or h % n or num_heads % n:
        return None
    hb = h // n
    heads_l = num_heads // n
    _note_chunks(1)   # single-hop deal: no chunk knob, still an island

    def _deal(qkv_l):
        blocks = [lax.dynamic_slice_in_dim(qkv_l, l * hb, hb, axis=-1)
                  for l in range(3)]
        recv = [lax.ppermute(blocks[l], MP_AXIS,
                             [(s, (3 * s + l) % n) for s in range(n)])
                for l in range(3)]
        st = jnp.stack(recv)
        d = lax.axis_index(MP_AXIS)
        outs = []
        for j in range(3):
            t = lax.dynamic_index_in_dim(st, (n * j + d) % 3, axis=0,
                                         keepdims=False)
            outs.append(t.reshape(t.shape[:-1] + (heads_l, head_dim)))
        return tuple(outs)

    out_spec = P(None, None, MP_AXIS, None)
    if b is None:
        def body(x_full, w_l):
            return _deal(x_full @ w_l)
        return shard_map(
            body, mesh=mesh,
            in_specs=(P(None, None, None), P(None, MP_AXIS)),
            out_specs=(out_spec,) * 3, check_vma=False)(x, w)

    def body(x_full, w_l, b_l):
        return _deal(x_full @ w_l + b_l)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, None), P(None, MP_AXIS), P(MP_AXIS)),
        out_specs=(out_spec,) * 3, check_vma=False)(x, w, b)
