"""Attention functionals.

``scaled_dot_product_attention`` routes to the Pallas flash-attention kernel
on TPU (paddle_tpu.kernels.flash_attention) and to a reference XLA
implementation elsewhere — the TPU-native answer to the reference's fused
FMHA (paddle/fluid/operators/fused/fmha_ref.h, fused_attention_op).
``packed_attention`` is the same kernel over a fused q/k/v projection's
output, read in place; ``packed_attention_supported`` says where it applies.
``rotary_embedding`` turns the leading lanes of each head by the token's
position (partial rotary: the lanes past ``rotary_dim`` pass untouched).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import call
from ...core.tensor import Tensor


def sdpa_reference_raw(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False,
                       scale=None, dropout_key=None):
    """Plain-XLA attention. q/k/v: (B, S, H, D) paddle layout."""
    bthd = q.ndim == 4
    if bthd:
        q_ = jnp.swapaxes(q, 1, 2)  # (B, H, S, D)
        k_ = jnp.swapaxes(k, 1, 2)
        v_ = jnp.swapaxes(v, 1, 2)
    else:
        q_, k_, v_ = q, k, v
    d = q_.shape[-1]
    s = scale if scale is not None else 1.0 / jnp.sqrt(jnp.asarray(d, q_.dtype))
    logits = jnp.einsum("...qd,...kd->...qk", q_, k_) * s
    if is_causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(causal, logits, jnp.asarray(-1e30, logits.dtype))
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, jnp.asarray(-1e30, logits.dtype))
        else:
            logits = logits + attn_mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q_.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("...qk,...kd->...qd", probs, v_)
    if bthd:
        out = jnp.swapaxes(out, 1, 2)
    return out


def rotary_embedding_raw(x, rotary_dim=None, theta=10000.0):
    """x (b, s, heads, d) -> the same with lanes 0..rotary_dim-1 of every
    head turned by the token's position (its index in the row) and the
    others untouched.  Half-split pairing (GPT-NeoX's, the
    published ``rotate_half``): lane i < rotary_dim / 2 pairs with lane i +
    rotary_dim / 2, at the angle ``position * theta^(-2 i / rotary_dim)``.
    Angles and the turn in float32, x's type out."""
    d = x.shape[-1]
    rotary_dim = d if rotary_dim is None else rotary_dim
    if rotary_dim % 2 or not 0 < rotary_dim <= d:
        raise ValueError("rotary_dim %r is no even number of a head's %d "
                         "lanes" % (rotary_dim, d))
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / rotary_dim)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rotary_dim].astype(jnp.float32)
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype),
         (x2 * cos + x1 * sin).astype(x.dtype), x[..., rotary_dim:]], axis=-1)


def rotary_embedding(x, rotary_dim=None, theta=10000.0):
    """:func:`rotary_embedding_raw` as a Tensor op."""
    return call(lambda a: rotary_embedding_raw(a, rotary_dim, theta), x,
                name="rotary_embedding")


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None,
                                 use_flash=True, sequence_parallel="auto"):
    """q/k/v: (batch, seq, heads, head_dim) — reference layout
    (python/paddle incubate FusedMultiHeadAttention input layout).

    SEQUENCE PARALLELISM: inside a shard_map trace with the framework's
    sequence-parallel axis 'sep' bound, the CONTRACT is that q/k/v are the
    LOCAL contiguous token shards, and attention runs via the ppermute
    ring-KV rotation over the axis (SURVEY §5.7).  Shapes/configurations
    the ring path cannot express there (attn_mask, active dropout, cached
    decode with q_len != k_len) raise rather than silently attending
    shard-locally.  Pass ``sequence_parallel=False`` for code inside a
    'sep' shard_map that has already gathered the full sequence.  Plain
    pjit/GSPMD traces never bind 'sep' manually and are unaffected.
    """
    from ...core import random as _rnd
    dropout_key = _rnd.next_key() if (dropout_p > 0.0 and training) else None
    if not training:
        dropout_p = 0.0

    def raw(q, k, v, m):
        if sequence_parallel:
            from ...distributed.collective import axis_in_trace
            if axis_in_trace("sep"):
                if dropout_p > 0.0 or q.ndim != 4 \
                        or q.shape[1] != k.shape[1]:
                    raise NotImplementedError(
                        "scaled_dot_product_attention under the 'sep' "
                        "sequence-parallel axis supports only dropout-free "
                        "self-attention (the ring schedule); disable "
                        "attention dropout under sequence parallelism, or "
                        "pass sequence_parallel=False if the sequence was "
                        "already gathered")
                if q.shape[2] % k.shape[2]:
                    # curated error before ring_attention's einsum would
                    # die with an opaque shape mismatch (ADVICE r3);
                    # divisible head counts route as grouped-query (the
                    # ring rotates the GROUPED K/V — wire bytes shrink by
                    # the group factor, r4 Weak #4)
                    raise NotImplementedError(
                        "grouped-query attention under the 'sep' ring "
                        "needs q heads (%d) divisible by k/v heads (%d)"
                        % (q.shape[2], k.shape[2]))
                mask = None
                if m is not None:
                    # ring contract: ADDITIVE mask, local q rows x global
                    # key axis (each ring step slices its shard's columns)
                    if m.dtype == jnp.bool_:
                        raise NotImplementedError(
                            "boolean attn_mask under the 'sep' ring is "
                            "not supported — pass an additive float mask "
                            "of shape (..., S_local, S_global) (its rows "
                            "are this rank's local q positions)")
                    if m.shape[-2] != q.shape[1]:
                        raise ValueError(
                            "attn_mask rows (%d) must equal the LOCAL "
                            "sequence shard (%d) under the 'sep' ring; "
                            "columns span the GLOBAL key axis"
                            % (m.shape[-2], q.shape[1]))
                    mask = m
                from ...distributed.ring_attention import ring_attention
                out = ring_attention(
                    jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                    jnp.swapaxes(v, 1, 2), "sep", causal=is_causal,
                    scale=scale, attn_mask=mask)  # ring is (B, H, S, D)
                return jnp.swapaxes(out, 1, 2)
        if q.ndim == 4 and k.shape[2] != q.shape[2]:
            # a key/value group: query head h reads key/value head
            # h // (heads / kv heads).  Expanded in front of the kernels,
            # which is exact (the group's gradient is the sum over its
            # query heads, as jnp.repeat's transpose gives it)
            if q.shape[2] % k.shape[2]:
                raise ValueError(
                    "grouped-query attention needs q heads (%d) divisible "
                    "by k/v heads (%d)" % (q.shape[2], k.shape[2]))
            group = q.shape[2] // k.shape[2]
            k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        if use_flash and m is None and dropout_p == 0.0:
            from ...kernels import flash_attention as fa
            if fa.supported(q, k):
                return fa.flash_attention_bshd(q, k, v, causal=is_causal,
                                               scale=scale)
        return sdpa_reference_raw(q, k, v, m, dropout_p, is_causal, scale,
                                  dropout_key)

    return call(raw, query, key, value, attn_mask, name="sdpa")


def packed_attention_supported(qkv, num_heads, dropout_p=0.0,
                               training=True) -> bool:
    """Whether :func:`packed_attention` applies to ``qkv`` (batch, seq,
    3 * heads * head_dim): exactly where ``scaled_dot_product_attention``
    of its three column slices would run the flash kernel on one device or
    under batch axes alone — dropout inactive, the shape supported
    (kernels.flash_attention.packed_supported), no live head axis ('mp':
    the fused projection's column shards are not head boundaries), and not
    inside a 'sep' shard_map (the ring takes q, k and v apart).  Decided
    from what the trace can see; there is no switch."""
    if dropout_p > 0.0 and training:
        return False
    from ...distributed.collective import axis_in_trace
    from ...kernels import flash_attention as fa
    return fa.packed_supported(qkv, num_heads) and not axis_in_trace("sep")


def packed_attention(qkv, num_heads, is_causal=False, scale=None):
    """Self-attention over a fused projection's output: qkv (batch, seq,
    3 * heads * head_dim) in ``[q | k | v]`` column order -> (batch, seq,
    heads, head_dim), equal bit for bit to ``scaled_dot_product_attention``
    of the three column slices.  The flash kernels read q, k and v where
    the projection wrote them: a Mosaic call cannot take a slice as an
    operand, so slicing first is a pass over the buffer and three written
    copies a layer.  Only where :func:`packed_attention_supported`."""
    def raw(x):
        from ...kernels import flash_attention as fa
        return fa.flash_attention_packed(x, num_heads, causal=is_causal,
                                         scale=scale)

    return call(raw, qkv, name="sdpa")
