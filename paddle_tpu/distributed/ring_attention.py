"""Ring attention — sequence/context parallelism over the 'sep' mesh axis.

The reference has NO sequence parallelism (SURVEY.md §5.7: no ring
attention/Ulysses/blockwise anywhere in the snapshot); this is a required
capability of the TPU build.  Design:

* ring_attention: each device holds a contiguous sequence shard of Q and of
  K/V.  K/V shards rotate around the ring via lax.ppermute (ICI neighbor
  exchange) in their ORIGINAL dtype (bf16 shards move 2 B/elem; an earlier
  revision rotated f32 and doubled the wire bytes) while each device
  accumulates blockwise-softmax statistics for its Q shard.  The inner
  block is itself BLOCKWISE: a remat'd scan over key chunks with online
  (max, sum, acc) statistics, so per-device memory is O(s_loc * chunk) in
  forward AND backward — never the (s_loc, s_loc) logits block.  Causality
  is enforced from global block positions (axis_index): ring steps holding
  strictly-future shards are skipped entirely (lax.cond — no MXU/VPU
  work), strictly-past shards run mask-free, and only the self shard pays
  the elementwise causal mask.
* ulysses_attention: the all-to-all variant — resharding (seq-sharded ->
  head-sharded) with two lax.all_to_all calls around ordinary local
  attention; composes with TP by splitting the head dim.

Both are plain jax functions intended for use inside shard_map (see
tests/test_distributed.py for the driving pattern); grads flow through
scan+ppermute+cond natively, with jax.checkpoint on the chunk body keeping
the backward blockwise too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_NEG_INF = -1e30

#: key-chunk width of the blockwise inner loop (elements of the rotating
#: K/V shard processed per online-softmax update)
DEFAULT_CHUNK = 512


#: below this admitted chunk width the inner scan degenerates into many
#: tiny sequential steps (a prime s_loc would otherwise silently run
#: chunk=1 — ~512x more scan steps; ADVICE r4)
_CHUNK_FLOOR = 128


def _chunk_for(s_blk: int, chunk: int) -> int:
    """Largest divisor of the K/V block length not exceeding ``chunk``."""
    c = min(chunk, s_blk)
    while s_blk % c:
        c -= 1
    if c < min(_CHUNK_FLOOR, s_blk):
        import warnings
        warnings.warn(
            "ring attention inner chunk degraded to %d for shard length "
            "%d (no divisor <= %d above %d) — pad the sequence shard to a "
            "multiple of a power of two to avoid a ~%dx slower inner scan"
            % (c, s_blk, chunk, _CHUNK_FLOOR, max(1, _CHUNK_FLOOR // c)))
    return c


def _pvary(a, axis_name):
    """scan carries inside shard_map are vma-typed; constants must be
    promoted to device-varying before entering the carry."""
    if axis_name is None:
        return a
    from .collective import ensure_varying
    return ensure_varying(a, axis_name)


def _blockwise_attn(q, k_blk, v_blk, scale, q_off, k_off, diag, mask_blk,
                    chunk, axis_name=None):
    """Blockwise (chunked, online-softmax) attention of the local Q shard
    against ONE rotating K/V shard.

    q: (B, H, Sq, D); k_blk/v_blk: (B, H, Sk, D) in their original dtype
    (bf16 contractions hit the MXU natively via preferred_element_type).
    q_off/k_off: traced global offsets of the shards (for the causal mask
    when ``diag``, a STATIC bool — the caller picks the masked or unmasked
    trace via lax.cond).  mask_blk: optional ADDITIVE f32 mask
    broadcastable to (B, H, Sq, Sk).  Returns (out, lse): out
    (B, H, Sq, D) f32 normalized within the block, lse (B, H, Sq) f32
    base-e.

    Memory: O(Sq * chunk) — the chunk body is jax.checkpoint'd so scan's
    backward recomputes the chunk logits instead of saving them.
    """
    b, h, sq, d = q.shape
    hk = k_blk.shape[1]
    sk = k_blk.shape[2]
    if h % hk:
        raise ValueError(
            "q heads (%d) must be a multiple of k/v heads (%d)" % (h, hk))
    g = h // hk
    rows = g * sq
    if g > 1:
        # grouped-query attention: fold the g query heads sharing one K/V
        # head into the ROW axis ((b, hk, g*sq, d) — rows ordered g-major),
        # so the contraction batches over the hk axis and K/V stay grouped
        # (this is what keeps ring wire bytes 1/g of dense, r4 Weak #4)
        q = q.reshape(b, hk, rows, d)
        if mask_blk is not None:
            if mask_blk.ndim == 4 and mask_blk.shape[1] == h:
                # per-q-head mask follows the head fold exactly
                mask_blk = mask_blk.reshape(b, hk, rows,
                                            mask_blk.shape[-1])
            else:
                # head-broadcast mask: repeat its row axis g times (the
                # row fold is (g, sq) — g-major)
                reps = [1] * mask_blk.ndim
                reps[-2] = g
                mask_blk = jnp.tile(mask_blk, reps)
    c = _chunk_for(sk, chunk)
    nck = sk // c

    def body(carry, ci):
        m, l, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(k_blk, ci * c, c, 2)
        vs = jax.lax.dynamic_slice_in_dim(v_blk, ci * c, c, 2)
        logits = jax.lax.dot_general(
            q, ks, (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32) * scale  # (B, HK, rows, c)
        if mask_blk is not None:
            mb = jax.lax.dynamic_slice_in_dim(
                mask_blk, ci * c, c, mask_blk.ndim - 1)
            logits = logits + mb.astype(jnp.float32)
        if diag:
            # elementwise causality on global positions — only the SELF
            # shard takes this branch (strictly-past shards run the
            # mask-free trace; strictly-future ones are skipped upstream).
            # With GQA the row axis is (g, sq) flattened: position = row
            # mod sq
            row_iota = jax.lax.broadcasted_iota(jnp.int32, (rows, c), 0)
            q_pos = q_off + jax.lax.rem(row_iota, jnp.int32(sq))
            k_pos = k_off + ci * c + jax.lax.broadcasted_iota(
                jnp.int32, (rows, c), 1)
            logits = jnp.where((k_pos <= q_pos)[None, None], logits,
                               jnp.float32(_NEG_INF))
        new_m = jnp.maximum(m, jnp.max(logits, axis=-1))
        corr = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m[..., None])
        new_l = l * corr + jnp.sum(p, axis=-1)
        new_acc = acc * corr[..., None] + jax.lax.dot_general(
            p.astype(vs.dtype), vs, (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32)
        return (new_m, new_l, new_acc), None

    init = (_pvary(jnp.full((b, hk, rows), _NEG_INF, jnp.float32),
                   axis_name),
            _pvary(jnp.zeros((b, hk, rows), jnp.float32), axis_name),
            _pvary(jnp.zeros((b, hk, rows, d), jnp.float32), axis_name))
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), init,
                                  jnp.arange(nck, dtype=jnp.int32))
    l_safe = jnp.maximum(l, 1e-30)
    out = acc / l_safe[..., None]
    lse = m + jnp.log(l_safe)
    if g > 1:
        out = out.reshape(b, h, sq, d)
        lse = lse.reshape(b, h, sq)
    return out, lse


def _pallas_inner_ok(q, k, attn_mask) -> bool:
    """Static gate: can the Pallas flash kernel serve as the ring inner?
    (TPU only; no additive mask — the kernel has no mask operand; no GQA —
    the kernel computes dense heads; supported shard shape.)"""
    import os
    if os.getenv("PADDLE_TPU_RING_INNER", "").lower() == "jnp":
        return False
    if jax.default_backend() != "tpu":
        return False
    if attn_mask is not None or q.shape[1] != k.shape[1]:
        return False
    b, h, s, d = q.shape
    if d not in (64, 128, 256) or s % 128:
        return False
    from ..kernels.flash_attention_pallas import max_supported_seq
    return s <= max_supported_seq(h, d)


def _flash_inner(q, k_blk, v_blk, causal, scale_py, interpret=False):
    """Pallas flash kernel as the ring inner: (B, H, S, D) shards in/out,
    (out f32, lse base-e (B, H, S) f32) — the same contract as
    :func:`_blockwise_attn`.  ``interpret`` is for tests on a CPU."""
    from ..kernels.flash_attention_pallas import \
        flash_attention_bshd_with_lse
    out, lse = flash_attention_bshd_with_lse(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k_blk, 1, 2),
        jnp.swapaxes(v_blk, 1, 2), causal=causal, scale=scale_py,
        interpret=interpret)
    return (jnp.swapaxes(out, 1, 2).astype(jnp.float32),
            jnp.swapaxes(lse, 1, 2))


def ring_attention(q, k, v, axis_name: str, causal: bool = True,
                   scale=None, attn_mask=None, chunk: int = DEFAULT_CHUNK):
    """Blockwise ring attention under shard_map.

    q, k, v: (B, H, S_local, D) — the local CONTIGUOUS sequence shard
    (equal length on every rank; global position of rank r's tokens is
    [r*S_local, (r+1)*S_local)).
    attn_mask: optional ADDITIVE mask, broadcastable to
    (B, H, S_local, S_global) — the caller's local q rows against the FULL
    key axis; each ring step slices the columns of the shard it holds.
    Returns (B, H, S_local, D) in q's dtype.

    INNER BLOCK: on TPU the per-shard attention runs the Pallas flash
    kernel (flash_attention_bshd_with_lse — its lse output is exactly the
    per-block statistic the ring combine needs, and its backward folds the
    lse cotangent as delta − dlse; r4 verdict #3).  The chunked-remat jnp
    blockwise inner remains the fallback (CPU meshes, GQA, additive
    masks) and the parity reference; force it with
    PADDLE_TPU_RING_INNER=jnp.
    """
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape
    if h % k.shape[1]:
        raise NotImplementedError(
            "ring_attention: q heads (%d) must be a multiple of k/v heads "
            "(%d) for grouped-query attention under the 'sep' ring"
            % (h, k.shape[1]))
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    use_pallas_inner = _pallas_inner_ok(q, k, attn_mask)
    scale_py = None
    if use_pallas_inner:
        try:
            scale_py = float(scale)   # static copy for the Pallas kernel
        except (TypeError, jax.errors.ConcretizationTypeError):
            use_pallas_inner = False  # traced scale: jnp inner handles it
    scale = jnp.float32(scale)
    if attn_mask is not None and attn_mask.shape[-2] != s_loc:
        raise ValueError(
            "ring_attention: attn_mask rows (%d) must cover the LOCAL q "
            "shard (%d); its columns cover the global key axis"
            % (attn_mask.shape[-2], s_loc))

    perm = [(i, (i + 1) % n) for i in range(n)]
    my = jnp.asarray(my, jnp.int32)       # x64 mode: keep index math i32
    n32 = jnp.int32(n)
    q_off = my * jnp.int32(s_loc)

    def step(carry, i):
        out_acc, lse_acc, k_cur, v_cur = carry
        src = jax.lax.rem(my - i + n32, n32)   # whose shard we hold
        k_off = src * jnp.int32(s_loc)

        def attend_with(diag):
            def fn(operand):
                k_b, v_b = operand
                if use_pallas_inner:
                    # diag == self shard (standard causal); past shards
                    # attend unmasked — the kernel covers both
                    ob, lb = _flash_inner(q, k_b, v_b, diag and causal,
                                          scale_py)
                    out_b, lse_b = ob, lb
                else:
                    mask_blk = None
                    if attn_mask is not None:
                        mask_blk = jax.lax.dynamic_slice_in_dim(
                            attn_mask, k_off, s_loc, attn_mask.ndim - 1)
                    out_b, lse_b = _blockwise_attn(
                        q, k_b, v_b, scale, q_off, k_off, diag, mask_blk,
                        chunk, axis_name)
                # flash-style two-level combine of normalized block results
                new_lse = jnp.logaddexp(lse_acc, lse_b)
                a = jnp.exp(lse_acc - new_lse)
                bb = jnp.exp(lse_b - new_lse)
                return (out_acc * a[..., None] + out_b * bb[..., None],
                        new_lse)
            return fn

        def skip(operand):
            return out_acc, lse_acc

        if causal:
            # strictly-future shards contribute nothing (no matmuls at
            # all); only the SELF shard pays the elementwise causal mask
            out_new, lse_new = jax.lax.cond(
                src > my, skip,
                lambda op: jax.lax.cond(src == my, attend_with(True),
                                        attend_with(False), op),
                (k_cur, v_cur))
        else:
            out_new, lse_new = attend_with(False)((k_cur, v_cur))
        # rotate K/V to the next device IN THEIR ORIGINAL DTYPE (bf16
        # shards move half the bytes of the old f32 rotation)
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (out_new, lse_new, k_next, v_next), None

    out0 = _pvary(jnp.zeros((b, h, s_loc, d), jnp.float32), axis_name)
    lse0 = _pvary(jnp.full((b, h, s_loc), _NEG_INF, jnp.float32), axis_name)

    (out, _lse, _k, _v), _ = jax.lax.scan(
        step, (out0, lse0, k, v), jnp.arange(n, dtype=jnp.int32))
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = True,
                      scale=None, attn_fn=None):
    """DeepSpeed-Ulysses style: all_to_all heads<->sequence, local attention,
    all_to_all back.  q/k/v: (B, H, S_local, D) with H divisible by the axis
    size; inside, each device sees (B, H/n, S_full, D)."""
    n = jax.lax.psum(1, axis_name)

    def seq_to_head(x):
        b, h, s_loc, d = x.shape
        x = x.reshape(b, n, h // n, s_loc, d)
        x = jnp.moveaxis(x, 1, 0)                      # (n, b, h/n, s_loc, d)
        x = jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                               tiled=False)            # n dim now = seq chunks
        x = jnp.moveaxis(x, 0, 3)                      # (b, h/n, s_loc, n, d)
        b2, hn, s_loc2, n2, d2 = x.shape
        # (b, h/n, n, s_loc, d) -> concat seq chunks in ring order
        return jnp.reshape(jnp.swapaxes(x, 2, 3), (b2, hn, n2 * s_loc2, d2))

    def head_to_seq(x):
        b, hn, s_full, d = x.shape
        s_loc = s_full // n
        x = x.reshape(b, hn, n, s_loc, d)
        x = jnp.moveaxis(x, 2, 0)                      # (n, b, h/n, s_loc, d)
        x = jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                               tiled=False)
        x = jnp.moveaxis(x, 0, 1)                      # (b, n, h/n, s_loc, d)
        return x.reshape(b, x.shape[1] * x.shape[2], s_loc, d)

    q2, k2, v2 = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    if attn_fn is None:
        def attn_fn(q_, k_, v_):
            d = q_.shape[-1]
            s = scale if scale is not None else 1.0 / (d ** 0.5)
            if _pallas_inner_ok(q_, k_, None):
                try:
                    s_py = float(s)
                except (TypeError, jax.errors.ConcretizationTypeError):
                    s_py = None       # traced scale: jnp inner below
                if s_py is not None:
                    # full local attention needs no lse — the plain flash
                    # custom_vjp serves directly (r4 verdict Weak #8)
                    from ..kernels.flash_attention_pallas import \
                        flash_attention_bshd_native
                    out = flash_attention_bshd_native(
                        jnp.swapaxes(q_, 1, 2), jnp.swapaxes(k_, 1, 2),
                        jnp.swapaxes(v_, 1, 2), causal=causal,
                        scale=s_py)
                    return jnp.swapaxes(out, 1, 2).astype(q_.dtype)
            # blockwise inner fallback: the gathered S_full axis is the
            # long one — never materialise (S_full, S_full) logits
            out, _ = _blockwise_attn(
                q_, k_, v_, jnp.float32(s), jnp.int32(0), jnp.int32(0),
                causal, None, DEFAULT_CHUNK, axis_name)
            return out.astype(q_.dtype)
    out = attn_fn(q2, k2, v2)
    return head_to_seq(out)
