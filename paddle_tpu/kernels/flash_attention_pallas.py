"""Pallas TPU flash-attention kernels (forward + backward).

Blockwise streaming-softmax attention (Flash-Attention style): running
(max, sum, acc) statistics in fp32, so score matrices never materialise in
HBM — O(S) memory instead of the reference FMHA's O(S^2)
(paddle/fluid/operators/fused/fmha_ref.h).

Layout: the kernels are NATIVE to the model's (B, S, H, D) activations,
viewed as (B, S, H*D).  Head groups are a GRID dimension over the folded
H*D axis (`hg` heads per cell so hg*D is lane-aligned, i.e. % 128), and the
per-head attention math runs as a static loop inside the cell.  This
removes the six (B,S,H,D) <-> (B,H,S,D) transposes per layer that a
head-major kernel forces around every call — measured ~9 ms/step of pure
HBM copies on the GPT-2 345M bench (PERF.md).

Forward: grid (B, n_hg, nq); the whole K/V sequence stays VMEM-resident and
is scanned with fori loops (measured faster at these shapes than streaming
K/V blocks through the grid — the extra grid steps only added overhead).
Causal q-blocks split the scan into mask-free fully-visible blocks and the
masked diagonal band.

Backward is ONE merged kernel producing dQ, dK and dV: the textbook
two-kernel FlashAttention-2 split recomputes the logits and dP matmuls
twice; merging halves that recompute and saves a launch per layer.
Grid = (B, n_hg, nk, nq) with both inner dims sequential: dK/dV accumulate
per key block in scratch (reset at qi==0), dQ accumulates across the whole
(nk, nq) sweep in a full-sequence f32 scratch written at the final step.
Causal masking skips fully-masked blocks via pl.when (no MXU/VPU work; the
static grid still streams the prefetch, which is the price of pipelining).
A fori-style backward (K/V outer, q scanned inside) was measured SLOWER
(47.6k vs 49.6k tokens/s on the 345M bench) — fwd and bwd optimum differ.

Variants (round 6): every kernel family is registered with the autotuner
(kernels/autotune.py) and the softmax/mask/pipeline machinery is variant-
selectable — the hand-tuned round-5 configuration is the "base" variant and
the default, so nothing changes until tuning runs or a config is pinned:

- ``bf16chain``: the streaming-softmax elementwise chain (mask select,
  running max, exp2, p) runs in bf16 — the VPU's 2x-throughput dtype — with
  the max/sum-exp2/correction STATISTICS still accumulated in f32, and p
  feeding the MXU in bf16 without the separate f32->bf16 cast.  Targets
  the 39 ms attention VPU chain directly (PERF.md "structural" item 1).
- ``iotafree``: causal band blocks classify visibility with ONE compare of
  a compile-time (BQ, BK) column-minus-row constant against the scalar
  block offset, replacing the two per-element broadcasted_iota builds +
  adds + compare — extends the round-5 causal-split win (which removed
  mask arithmetic from fully-visible blocks) into the band blocks.
- ``parq`` (fwd, resident path): per-q-block lse output blocks instead of
  the revisited whole-sequence lse slice, which lets all three grid dims
  carry "parallel" dimension_semantics.
- ``pipelined`` (fwd): K/V stay in HBM (ANY memory space) and the kernel
  double-buffers block_k-sized chunks VMEM-ward with explicit async
  copies, overlapping the K/V fetch of block i+1 with the softmax chain of
  block i — the streamed forward's copy/compute overlap at sub-grid
  granularity.

All variants have interpret-mode parity tests vs the O(S^2) reference
(tests/test_flash_variants.py).
"""
from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.dtype import x64_scope


def _block_env(name, default):
    """Power-of-two >=128 only: the divisibility-fallback loop in
    flash_attention_bshd halves the block until it divides the sequence, so
    a non-power-of-two would turn supported() shapes into dispatch errors."""
    raw = os.getenv(name)
    if not raw:
        return default
    try:
        v = int(raw)
    except ValueError:
        return default
    if v < 128 or v & (v - 1):
        return default
    return v


DEFAULT_BLOCK_Q = _block_env("PADDLE_TPU_FLASH_BLOCK_Q", 512)
DEFAULT_BLOCK_K = _block_env("PADDLE_TPU_FLASH_BLOCK_K", 512)
_NEG_INF = -1e30
# The streaming softmax runs in BASE 2: folding log2(e) into the logits
# scale turns every exp into the VPU's native exp2 (jnp.exp lowers to
# exp2 + a multiply per element, and the softmax exp over b*h*s^2 logits
# is the kernel's dominant VPU cost).  lse is therefore stored in base-2
# units; the backward consumes it with exp2 as well, and d/d(qk) keeps the
# plain base-e `scale` factor (dS = scale * P * (dP - delta) regardless).
_LOG2E = 1.4426950408889634

_SEQ2 = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"))

#: A/B flag: mask the causal band by multiplying p after exp2 (max over
#: unmasked logits) instead of the -inf select before it
_BAND_MUL = os.getenv("PADDLE_TPU_FLASH_BANDMUL", "0") == "1"

#: variant features understood by the forward / backward kernels
_FWD_FEATURES = frozenset({"bf16chain", "iotafree", "parq", "pipelined"})
_BWD_FEATURES = frozenset({"bf16chain", "iotafree"})


def variant_features(variant, allowed=_FWD_FEATURES):
    """'bf16chain+iotafree' -> frozenset — validated against ``allowed``
    ('base' or '' is the empty set)."""
    if not variant or variant == "base":
        return frozenset()
    feats = frozenset(variant.split("+"))
    bad = feats - allowed
    if bad:
        raise ValueError("unknown flash variant feature(s) %s in %r "
                         "(allowed: %s)" % (sorted(bad), variant,
                                            sorted(allowed)))
    return feats


def canon_variant(feats) -> str:
    return "+".join(sorted(feats)) if feats else "base"


def bwd_variant_of(variant: str) -> str:
    """Strip forward-only features (parq/pipelined) for the backward."""
    return canon_variant(variant_features(variant) & _BWD_FEATURES)


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the vma (varying-manual-axes) of ``like``
    — pallas_call outputs inside a shard_map must declare how they vary
    (the ring-attention inner runs these kernels under manual axes)."""
    try:
        vma = jax.typeof(like).vma
    except Exception:
        vma = None
    if vma:
        try:
            return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
        except TypeError:
            pass
    return jax.ShapeDtypeStruct(shape, dtype)


def _i32(v):
    return jnp.asarray(v, jnp.int32)


def _pid(i):
    # strong int32: program_id is weakly typed and x64 mode would promote
    # its arithmetic to i64, which mosaic cannot lower
    return jax.lax.convert_element_type(pl.program_id(i), jnp.int32)


# VMEM budget for the forward's resident K+V per grid cell
# (s * hg*d * 2 arrays * 2 B bf16, double-buffered by the pipeline);
# sequences whose K/V exceed it take the grid-streamed forward instead.
_RESIDENT_KV_BUDGET = 4 * 1024 * 1024
# VMEM budget for the backward's full-sequence dq accumulator
# (s * hg*d * 4 B f32) — THE sequence-length bound of the Pallas path;
# beyond it the sequence axis must shard (ring attention, SURVEY §5.7).
# 4MB empirically: 8MB of dq scratch plus streamed blocks + dk/dv scratch
# + lse/delta overflowed the 16MB VMEM by 4.5MB at s=8192.
_DQ_SCRATCH_BUDGET = 4 * 1024 * 1024


def _aligned_groups(h: int, d: int):
    out = [hg for hg in (8, 4, 2, 1)
           if h % hg == 0 and (hg * d) % 128 == 0]
    if not out:
        out = [h]  # whole folded axis: legal regardless of alignment
    return out


def _pick_head_group(h: int, d: int, s: int):
    """Heads per grid cell: hg*d must be lane-aligned (%128) and divide h.
    Picks the LARGEST group with hg*d <= 256 — bigger groups amortize grid
    overhead (+0.8k tokens/s measured on the 345M bench; hg*d=512 blew
    VMEM by 156KB at s=1024) — whose backward dq scratch still fits at this
    sequence length (long sequences shrink the group)."""
    def bwd_fits(hg):
        return s * hg * d * 4 <= _DQ_SCRATCH_BUDGET

    forced = _valid_forced_group(h, d)
    if forced is not None:
        return forced
    groups = _aligned_groups(h, d)
    for hg in groups:            # largest first
        if hg * d <= 256 and bwd_fits(hg):
            return hg
    # no group fits the merged backward's full-seq scratch: the SPLIT
    # backward (O(block) VMEM) takes over — pick by block size alone
    for hg in groups:
        if hg * d <= 256:
            return hg
    return groups[-1]


def _kv_fits_resident(s: int, hgd: int) -> bool:
    """K+V bf16, double-buffered — must match _flash_fwd_inner's dispatch
    between the resident and streamed forward."""
    return s * hgd * 2 * 2 <= _RESIDENT_KV_BUDGET


def _valid_forced_group(h: int, d: int):
    raw = os.getenv("PADDLE_TPU_FLASH_HEAD_GROUP")
    if not raw:
        return None
    try:
        hg = int(raw)
    except ValueError:
        return None
    if h % hg == 0 and ((hg * d) % 128 == 0 or hg == h):
        return hg
    return None


def _pick_fwd_head_group(h: int, d: int, s: int, hg_b: int) -> int:
    """The forward has no full-sequence scratch, so it can afford a larger
    group (up to hg*d = 512) when the resident K/V still fits — fewer grid
    cells amortize per-cell overhead.  Falls back to the backward's group.
    A VALID env override (PADDLE_TPU_FLASH_HEAD_GROUP) pins both
    directions; invalid values are ignored in both pickers."""
    if _valid_forced_group(h, d) is not None:
        return hg_b
    for hg in _aligned_groups(h, d):      # largest first
        if hg * d <= 512 and _kv_fits_resident(s, hg * d):
            # the first admissible candidate is always >= hg_b (hg_b
            # satisfies stricter constraints), so no max() needed
            return hg
    return hg_b


#: VMEM allowance for the full-sequence lse+delta blocks the kernels keep
#: resident per grid cell ((1,1,hg,nq,bq) each = hg*s*4 B); the rest of
#: the 16 MB budget is operand blocks + scratch + double buffering
_LSE_RESIDENCY_BUDGET = 8 * 1024 * 1024


def max_supported_seq(h: int, d: int) -> int:
    """Longest sequence the Pallas path supports end-to-end, derived from
    the lse/delta VMEM residency at THIS (h, d)'s head group — a flat cap
    admitted shapes (e.g. d=32 -> hg=8) whose hg*s*4-byte lse blocks fail
    Mosaic allocation at compile time (ADVICE r3).  Beyond the cap the
    sequence axis should shard (ring/Ulysses, SURVEY §5.7)."""
    s = 256 * 1024
    while s >= 1024:
        hg = _pick_head_group(h, d, s)
        if 2 * hg * s * 4 <= _LSE_RESIDENCY_BUDGET:
            return s
        s //= 2
    return 1024


# ---------------------------------------------------------------------------
# shared per-block math (variant-selectable)
# ---------------------------------------------------------------------------

def _band_diff(block_q: int, block_k: int):
    """(BQ, BK) column-minus-row index matrix for the iotafree band mask:
    vis[i, j] = (col0 + j <= row0 + i) = (j - i <= row0 - col0), so a band
    block's whole mask is ONE compare of this (block-independent) matrix
    against the scalar block offset.  Built from in-kernel iotas — Pallas
    rejects captured host constants — but hoisted out of
    the per-k-block loop by the callers (and loop-invariant for Mosaic),
    unlike the base path's per-block row_ids/col_ids builds."""
    return jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) - \
        jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)


def _cell_vis(row0, col0, block_q, block_k, iotafree):
    """Causal visibility mask for the (row0, col0) block (scalars are the
    absolute first row/col of the block)."""
    if iotafree:
        return _band_diff(block_q, block_k) <= (row0 - col0)
    row_ids = row0[None, None] + \
        jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    col_ids = col0[None, None] + \
        jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return col_ids <= row_ids


def _online_step(q, k, v, m, l, acc, vis, scale, bf16chain, band_mul=False):
    """One streaming-softmax accumulation over a K/V block.

    (m, l, acc) are the running f32 statistics; ``vis`` is None (unmasked
    block) or the (BQ, BK) visibility mask; ``band_mul`` applies vis by
    multiplying p AFTER the exp2 instead of the -inf select before it.
    bf16chain runs the elementwise chain (select, exp2, p) in bf16 with
    f32 statistics — p then feeds the MXU without a separate cast.
    """
    # bf16 x bf16 -> f32 is the MXU's native mode; upcasting operands
    # first quarters matmul throughput
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.float32(scale * _LOG2E)
    if bf16chain:
        lb = logits.astype(jnp.bfloat16)
        if vis is not None and not band_mul:
            lb = jnp.where(vis, lb, jnp.bfloat16(_NEG_INF))
        # band_mul: run the max over UNMASKED logits (an over-estimate only
        # shrinks p — lse stays exact) and zero the future columns AFTER
        # the exp2 with one multiply, replacing the -inf select
        new_m = jnp.maximum(m, jnp.max(lb, axis=-1).astype(jnp.float32))
        p = jnp.exp2(lb - new_m.astype(jnp.bfloat16)[:, None])
        if vis is not None and band_mul:
            p = p * vis.astype(jnp.bfloat16)
        psum = jnp.sum(p, axis=-1, dtype=jnp.float32)
    else:
        if vis is not None and not band_mul:
            logits = jnp.where(vis, logits, jnp.float32(_NEG_INF))
        new_m = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp2(logits - new_m[:, None])
        if vis is not None and band_mul:
            p = p * vis.astype(jnp.float32)
        psum = jnp.sum(p, axis=-1)
    correction = jnp.exp2(m - new_m)
    new_l = l * correction + psum
    new_acc = acc * correction[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return new_m, new_l, new_acc


def _bwd_head_math(q, k, v, do, lse, delta, vis, scale, bf16chain,
                   want_dq=True, want_dkv=True):
    """The per-head backward block math shared by the merged/dq/dkv
    kernels: recompute p from (q, k, lse), then the requested subset of
    {dv += P^T dO, dk += dS^T Q, dq += dS K}.  Returns a dict of f32 block
    contributions."""
    logits = jnp.float32(scale * _LOG2E) * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (BQ, BK)
    if bf16chain:
        p = jnp.exp2((logits - lse[:, None]).astype(jnp.bfloat16))
        if vis is not None:
            p = jnp.where(vis, p, jnp.bfloat16(0.0))
    else:
        p = jnp.exp2(logits - lse[:, None])
        if vis is not None:
            p = jnp.where(vis, p, jnp.float32(0.0))
    out = {}
    if want_dkv:
        pc = p.astype(do.dtype)
        # dV += P^T dO
        out["dv"] = jax.lax.dot_general(
            pc, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (BK, D)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (BQ, BK)
    if bf16chain:
        ds = (p * (dp - delta[:, None]).astype(jnp.bfloat16)).astype(q.dtype)
    else:
        ds = (p * (dp - delta[:, None])).astype(q.dtype)
    if want_dkv:
        # dK += dS^T Q
        out["dk"] = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (BK, D)
    if want_dq:
        # dQ += dS K
        out["dq"] = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (BQ, D)
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, scale, hg,
                d, block_k, bf16chain=False, iotafree=False, parq=False):
    # q/o: (1, BQ, HG*D); k/v: (1, S, HG*D) — the WHOLE sequence resident
    # in VMEM, scanned with a fori loop (measured faster than grid-streamed
    # K/V blocks at these shapes: the pipeline only added grid overhead);
    # lse: (1, 1, HG, NQ, BQ) — or per-q-block (1, 1, 1, HG, BQ) under parq
    # (q-block-major, so the block's last two dims are the whole (HG, BQ)
    # tile Mosaic requires; the wrapper swaps it back).
    block_q = q_ref.shape[1]
    s = k_ref.shape[1]
    qi = _pid(2)
    row0 = jax.lax.mul(qi, _i32(block_q))

    if causal and not iotafree:
        row_ids = row0[None, None] + \
            jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    if causal and iotafree:
        diff = _band_diff(block_q, block_k)

    for hh in range(hg):
        sl = slice(hh * d, (hh + 1) * d)
        q = q_ref[0, :, sl]                                   # (BQ, D)

        def make_body(masked):
            def body(kb, carry):
                m, l, acc = carry
                start = jax.lax.mul(kb, _i32(block_k))
                k = k_ref[0, pl.ds(start, block_k), sl]
                v = v_ref[0, pl.ds(start, block_k), sl]
                vis = None
                if masked:
                    if iotafree:
                        vis = diff <= (row0 - start)
                    else:
                        col_ids = start[None, None] + \
                            jax.lax.broadcasted_iota(
                                jnp.int32, (block_q, block_k), 1)
                        vis = col_ids <= row_ids
                return _online_step(q, k, v, m, l, acc, vis, scale,
                                    bf16chain,
                                    band_mul=masked and _BAND_MUL)
            return body

        init = (jnp.full((block_q,), jnp.float32(_NEG_INF), jnp.float32),
                jnp.zeros((block_q,), jnp.float32),
                jnp.zeros((block_q, d), jnp.float32))
        if causal:
            # fully-visible blocks skip the mask arithmetic; the diagonal
            # band (block_q // block_k blocks) applies it
            assert block_q % block_k == 0
            ratio = _i32(block_q // block_k)
            num_full = jax.lax.mul(qi, ratio)
            carry = jax.lax.fori_loop(_i32(0), num_full, make_body(False),
                                      init)
            m, l, acc = jax.lax.fori_loop(num_full,
                                          jax.lax.add(num_full, ratio),
                                          make_body(True), carry)
        else:
            m, l, acc = jax.lax.fori_loop(_i32(0), _i32(s // block_k),
                                          make_body(False), init)
        l_safe = jnp.maximum(l, jnp.float32(1e-30))
        o_ref[0, :, sl] = (acc / l_safe[:, None]).astype(o_ref.dtype)
        # lse in base-2 units: m is already log2-scaled
        lse_row = (m + jnp.log(l_safe) * jnp.float32(_LOG2E))[None, :]
        if parq:
            lse_ref[0, 0, 0, pl.ds(hh, 1), :] = lse_row
        else:
            lse_ref[0, 0, hh, pl.ds(qi, 1), :] = lse_row


def _fwd_kernel_streamed(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc,
                         acc_sc, *, causal, scale, hg, d, nk,
                         bf16chain=False, iotafree=False):
    # q/o: (1, BQ, HG*D); k/v: (1, BK, HG*D) — ki-th block, streamed by the
    # grid; lse: (1, 1, HG, NQ, BQ); scratch m/l: (HG, BQ) f32,
    # acc: (BQ, HG*D) f32, persistent across the sequential ki iterations.
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    qi = _pid(2)
    ki = _pid(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _attend(masked):
        vis = None
        if masked:
            vis = _cell_vis(jax.lax.mul(qi, _i32(block_q)),
                            jax.lax.mul(ki, _i32(block_k)),
                            block_q, block_k, iotafree)
        for hh in range(hg):
            sl = slice(hh * d, (hh + 1) * d)
            q = q_ref[0, :, sl]                               # (BQ, D)
            k = k_ref[0, :, sl]                               # (BK, D)
            v = v_ref[0, :, sl]
            new_m, new_l, new_acc = _online_step(
                q, k, v, m_sc[hh], l_sc[hh], acc_sc[:, sl], vis, scale,
                bf16chain)
            l_sc[hh] = new_l
            acc_sc[:, sl] = new_acc
            m_sc[hh] = new_m

    if causal:
        # split visible blocks into fully-visible (no mask arithmetic —
        # the iota/where VPU work is significant at these shapes) and the
        # diagonal band (masked); the two pl.when branches are disjoint
        first_row = jax.lax.mul(qi, _i32(block_q))
        last_row = first_row + _i32(block_q - 1)
        last_col = jax.lax.mul(ki, _i32(block_k)) + _i32(block_k - 1)
        fully_visible = last_col <= first_row
        diagonal = jnp.logical_and(last_col > first_row,
                                   jax.lax.mul(ki, _i32(block_k)) <=
                                   last_row)

        @pl.when(fully_visible)
        def _compute_full():
            _attend(False)

        @pl.when(diagonal)
        def _compute_diag():
            _attend(True)
    else:
        _attend(False)

    @pl.when(ki == nk - 1)
    def _finalize():
        for hh in range(hg):
            sl = slice(hh * d, (hh + 1) * d)
            l_safe = jnp.maximum(l_sc[hh], jnp.float32(1e-30))
            o_ref[0, :, sl] = (acc_sc[:, sl] /
                               l_safe[:, None]).astype(o_ref.dtype)
            # lse in base-2 units (see _LOG2E)
            lse_ref[0, 0, hh, pl.ds(qi, 1), :] = \
                (m_sc[hh] + jnp.log(l_safe) * jnp.float32(_LOG2E))[None, :]


def _fwd_kernel_pipelined(q_ref, k_any, v_any, o_ref, lse_ref, k_sc, v_sc,
                          sem, *, causal, scale, hg, d, block_k, nk,
                          bf16chain=False, iotafree=False):
    """Forward with EXPLICIT K/V streaming: K/V stay in HBM (ANY memory
    space) and block_k-sized chunks are double-buffered into VMEM scratch
    with async copies, so the fetch of chunk i+1 overlaps the softmax chain
    of chunk i.  Grid (B, n_hg, nq) like the resident kernel; O(block_k)
    K/V VMEM instead of O(S).  Under causal the scan stops after the
    diagonal band; band blocks are classified per-iteration (scalar
    compare), so unlike the resident kernel there is no separate mask-free
    loop — the variant trades that split for the copy overlap."""
    block_q = q_ref.shape[1]
    hgd = hg * d
    bi = _pid(0)
    g = _pid(1)
    qi = _pid(2)
    row0 = jax.lax.mul(qi, _i32(block_q))
    col_base = jax.lax.mul(g, _i32(hgd))

    if causal:
        # only blocks up to the band end attend; rest are strictly future
        assert block_q % block_k == 0
        kend = jax.lax.mul(qi + 1, _i32(block_q // block_k))
    else:
        kend = _i32(nk)

    def kv_dma(slot, kb):
        start = jax.lax.mul(kb, _i32(block_k))
        ck = pltpu.make_async_copy(
            k_any.at[bi, pl.ds(start, block_k), pl.ds(col_base, hgd)],
            k_sc.at[slot], sem.at[slot, 0])
        cv = pltpu.make_async_copy(
            v_any.at[bi, pl.ds(start, block_k), pl.ds(col_base, hgd)],
            v_sc.at[slot], sem.at[slot, 1])
        return ck, cv

    ck0, cv0 = kv_dma(0, _i32(0))
    ck0.start()
    cv0.start()

    def body(kb, carry):
        ms, ls, accs = carry     # per-head tuples: (BQ,), (BQ,), (BQ, D)
        slot = jax.lax.rem(kb, _i32(2))
        nxt = jax.lax.rem(kb + 1, _i32(2))

        @pl.when(kb + 1 < kend)
        def _prefetch():
            ckn, cvn = kv_dma(nxt, kb + 1)
            ckn.start()
            cvn.start()

        ck, cv = kv_dma(slot, kb)
        ck.wait()
        cv.wait()
        start = jax.lax.mul(kb, _i32(block_k))
        vis = None
        if causal:
            # band blocks need the mask; fully-visible ones get vis=True
            # everywhere (the scalar classification is folded into the
            # mask itself — cheaper than a pl.when split inside fori)
            vis = _cell_vis(row0, start, block_q, block_k, iotafree)
        new_ms, new_ls, new_accs = [], [], []
        for hh in range(hg):
            sl = slice(hh * d, (hh + 1) * d)
            nm, nl, na = _online_step(
                q_ref[0, :, sl], k_sc[slot, :, sl], v_sc[slot, :, sl],
                ms[hh], ls[hh], accs[hh], vis, scale, bf16chain)
            new_ms.append(nm)
            new_ls.append(nl)
            new_accs.append(na)
        return tuple(new_ms), tuple(new_ls), tuple(new_accs)

    init = (tuple(jnp.full((block_q,), jnp.float32(_NEG_INF), jnp.float32)
                  for _ in range(hg)),
            tuple(jnp.zeros((block_q,), jnp.float32) for _ in range(hg)),
            tuple(jnp.zeros((block_q, d), jnp.float32)
                  for _ in range(hg)))
    ms, ls, accs = jax.lax.fori_loop(_i32(0), kend, body, init)
    for hh in range(hg):
        sl = slice(hh * d, (hh + 1) * d)
        l_safe = jnp.maximum(ls[hh], jnp.float32(1e-30))
        o_ref[0, :, sl] = (accs[hh] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, hh, pl.ds(qi, 1), :] = \
            (ms[hh] + jnp.log(l_safe) * jnp.float32(_LOG2E))[None, :]


def _flash_fwd(q3, k3, v3, causal, scale, d, interpret, spec):
    # trace with x64 off: the global x64 mode (needed for paddle's int64
    # semantics) surfaces i64/f64 intermediates that mosaic cannot lower
    with x64_scope(False):
        return _flash_fwd_inner(q3, k3, v3, causal, scale, d, interpret,
                                spec)


def _flash_fwd_inner(q3, k3, v3, causal, scale, d, interpret, spec):
    variant, block_q, block_k, hg = spec
    feats = variant_features(variant, _FWD_FEATURES)
    bf16chain = "bf16chain" in feats
    iotafree = "iotafree" in feats
    b, s, hd = q3.shape
    sk = k3.shape[1]
    n_hg = hd // (hg * d)
    nq = s // block_q
    nk = sk // block_k
    hgd = hg * d
    q_spec3 = pl.BlockSpec((1, block_q, hgd), lambda bi, g, i: (bi, i, g))
    lse_shape = _sds((b, n_hg, hg, nq, block_q), jnp.float32, q3)
    out_shape = _sds((b, s, hd), q3.dtype, q3)
    if "pipelined" in feats:
        # explicit double-buffered K/V DMA — O(block_k) K/V VMEM at ANY
        # sequence length (an alternative to both the resident and the
        # grid-streamed paths; the autotuner decides when it wins)
        kernel = functools.partial(
            _fwd_kernel_pipelined, causal=causal, scale=scale, hg=hg, d=d,
            block_k=block_k, nk=nk, bf16chain=bf16chain, iotafree=iotafree)
        out, lse = pl.pallas_call(
            kernel,
            grid=(b, n_hg, nq),
            in_specs=[q_spec3,
                      pl.BlockSpec(memory_space=pltpu.ANY),
                      pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=[
                q_spec3,
                pl.BlockSpec((1, 1, hg, nq, block_q),
                             lambda bi, g, i: (bi, g, 0, 0, 0)),
            ],
            out_shape=[out_shape, lse_shape],
            scratch_shapes=[
                pltpu.VMEM((2, block_k, hgd), k3.dtype),
                pltpu.VMEM((2, block_k, hgd), v3.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name="flash_fwd",
            interpret=interpret,
        )(q3, k3, v3)
        return out, lse
    if _kv_fits_resident(sk, hgd):
        # fast path: whole K/V resident per cell, fori scan (measured
        # fastest at bench shapes)
        parq = "parq" in feats
        kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                                   hg=hg, d=d, block_k=block_k,
                                   bf16chain=bf16chain, iotafree=iotafree,
                                   parq=parq)
        kv_spec = pl.BlockSpec((1, sk, hgd), lambda bi, g, i: (bi, 0, g))
        if parq:
            # per-q-block lse blocks: nothing is revisited, so every grid
            # dim can carry "parallel" dimension_semantics.  Stored
            # q-block-major — a (1, BQ) tail is not (8, 128)-tileable, the
            # whole (HG, BQ) tail is — and swapped back below
            lse_shape = _sds((b, n_hg, nq, hg, block_q), jnp.float32, q3)
            lse_spec = pl.BlockSpec((1, 1, 1, hg, block_q),
                                    lambda bi, g, i: (bi, g, i, 0, 0))
            sem = ("parallel", "parallel", "parallel")
        else:
            # whole folded lse slice per (b, head-group), revisited
            # across the sequential q-block dim
            lse_spec = pl.BlockSpec((1, 1, hg, nq, block_q),
                                    lambda bi, g, i: (bi, g, 0, 0, 0))
            sem = ("parallel", "parallel", "arbitrary")
        out, lse = pl.pallas_call(
            kernel,
            grid=(b, n_hg, nq),
            in_specs=[q_spec3, kv_spec, kv_spec],
            out_specs=[q_spec3, lse_spec],
            out_shape=[out_shape, lse_shape],
            compiler_params=pltpu.CompilerParams(dimension_semantics=sem),
            name="flash_fwd",
            interpret=interpret,
        )(q3, k3, v3)
        return out, (jnp.swapaxes(lse, 2, 3) if parq else lse)
    # long-sequence path: K/V blocks streamed by the grid — O(block) VMEM,
    # keeps the O(S) capability for sequences whose K/V don't fit resident
    kernel = functools.partial(_fwd_kernel_streamed, causal=causal,
                               scale=scale, hg=hg, d=d, nk=nk,
                               bf16chain=bf16chain, iotafree=iotafree)
    q_spec = pl.BlockSpec((1, block_q, hgd), lambda bi, g, i, j: (bi, i, g))
    kv_spec = pl.BlockSpec((1, block_k, hgd), lambda bi, g, i, j: (bi, j, g))
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, n_hg, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, 1, hg, nq, block_q),
                         lambda bi, g, i, j: (bi, g, 0, 0, 0)),
        ],
        out_shape=[out_shape, lse_shape],
        scratch_shapes=[
            pltpu.VMEM((hg, block_q), jnp.float32),
            pltpu.VMEM((hg, block_q), jnp.float32),
            pltpu.VMEM((block_q, hgd), jnp.float32),
        ],
        compiler_params=_SEQ2,
        name="flash_fwd",
        interpret=interpret,
    )(q3, k3, v3)
    return out, lse


# ---------------------------------------------------------------------------
# backward (merged dQ/dK/dV + split dQ / dKV kernels)
# ---------------------------------------------------------------------------

def _apply_causal_split(compute, causal, qi, ki, block_q, block_k):
    """Run ``compute(masked)`` under the causal block taxonomy: skipped
    (strictly-future), fully-visible (no mask arithmetic), or diagonal
    band (mask applied).  Non-causal runs unconditionally unmasked."""
    if not causal:
        compute(False)
        return
    first_row = jax.lax.mul(qi, _i32(block_q))
    last_row = first_row + _i32(block_q - 1)
    first_col = jax.lax.mul(ki, _i32(block_k))
    last_col = first_col + _i32(block_k - 1)
    fully_visible = last_col <= first_row
    diagonal = jnp.logical_and(last_col > first_row, first_col <= last_row)
    pl.when(fully_visible)(lambda: compute(False))
    pl.when(diagonal)(lambda: compute(True))


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, *,
                causal, scale, hg, d, nq, nk, bf16chain=False,
                iotafree=False):
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]
    ki = _pid(2)
    qi = _pid(3)

    @pl.when(jnp.logical_and(ki == 0, qi == 0))
    def _init_dq():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def _compute(masked):
        vis = None
        if masked:
            vis = _cell_vis(jax.lax.mul(qi, _i32(block_q)),
                            jax.lax.mul(ki, _i32(block_k)),
                            block_q, block_k, iotafree)
        row0 = jax.lax.mul(qi, _i32(block_q))
        for hh in range(hg):
            sl = slice(hh * d, (hh + 1) * d)
            g = _bwd_head_math(
                q_ref[0, :, sl], k_ref[0, :, sl], v_ref[0, :, sl],
                do_ref[0, :, sl],
                lse_ref[0, 0, hh, pl.ds(qi, 1), :][0],       # (BQ,) base-2
                delta_ref[0, 0, hh, pl.ds(qi, 1), :][0],     # (BQ,) f32
                vis, scale, bf16chain)
            dv_sc[:, sl] = dv_sc[:, sl] + g["dv"]
            dk_sc[:, sl] = dk_sc[:, sl] + g["dk"]
            # dQ rows qi accumulate in the full-sequence scratch
            dq_sc[pl.ds(row0, block_q), sl] = \
                dq_sc[pl.ds(row0, block_q), sl] + g["dq"]

    # fully-visible blocks skip the iota/where mask arithmetic entirely —
    # only the diagonal band pays it (the same split the streamed forward
    # uses; the two pl.when conditions are disjoint)
    _apply_causal_split(_compute, causal, qi, ki, block_q, block_k)

    @pl.when(qi == nq - 1)
    def _finalize_kv():
        dk_ref[0] = (jnp.float32(scale) * dk_sc[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(ki == nk - 1, qi == nq - 1))
    def _finalize_q():
        dq_ref[0] = (jnp.float32(scale) * dq_sc[...]).astype(dq_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_sc, *, causal, scale, hg, d, nk,
                   bf16chain=False, iotafree=False):
    """dQ-only backward for LONG sequences: grid (b, n_hg, nq, nk) with ki
    innermost, so dq accumulates in a BLOCK-sized scratch (no full-sequence
    scratch — the merged kernel's 16k+ VMEM blocker, PERF.md)."""
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]
    qi = _pid(2)
    ki = _pid(3)

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def _compute(masked):
        vis = None
        if masked:
            vis = _cell_vis(jax.lax.mul(qi, _i32(block_q)),
                            jax.lax.mul(ki, _i32(block_k)),
                            block_q, block_k, iotafree)
        for hh in range(hg):
            sl = slice(hh * d, (hh + 1) * d)
            g = _bwd_head_math(
                q_ref[0, :, sl], k_ref[0, :, sl], v_ref[0, :, sl],
                do_ref[0, :, sl],
                lse_ref[0, 0, hh, pl.ds(qi, 1), :][0],       # base-2
                delta_ref[0, 0, hh, pl.ds(qi, 1), :][0],
                vis, scale, bf16chain, want_dkv=False)
            dq_sc[:, sl] = dq_sc[:, sl] + g["dq"]

    _apply_causal_split(_compute, causal, qi, ki, block_q, block_k)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = (jnp.float32(scale) * dq_sc[...]).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_sc, dv_sc, *, causal, scale, hg, d,
                    nq, bf16chain=False, iotafree=False):
    """dK/dV backward (ki outer, qi inner) — the merged kernel minus the
    full-sequence dq scratch; pairs with _bwd_dq_kernel for long seqs."""
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]
    ki = _pid(2)
    qi = _pid(3)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def _compute(masked):
        vis = None
        if masked:
            vis = _cell_vis(jax.lax.mul(qi, _i32(block_q)),
                            jax.lax.mul(ki, _i32(block_k)),
                            block_q, block_k, iotafree)
        for hh in range(hg):
            sl = slice(hh * d, (hh + 1) * d)
            g = _bwd_head_math(
                q_ref[0, :, sl], k_ref[0, :, sl], v_ref[0, :, sl],
                do_ref[0, :, sl],
                lse_ref[0, 0, hh, pl.ds(qi, 1), :][0],
                delta_ref[0, 0, hh, pl.ds(qi, 1), :][0],
                vis, scale, bf16chain, want_dq=False)
            dv_sc[:, sl] = dv_sc[:, sl] + g["dv"]
            dk_sc[:, sl] = dk_sc[:, sl] + g["dk"]

    _apply_causal_split(_compute, causal, qi, ki, block_q, block_k)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (jnp.float32(scale) * dk_sc[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _fold_lse(lse, b, h, hg, block_q):
    """(b, n_hg_f, hg_f, nq_f, bq_f) -> (b, h/hg, hg, s/bq, bq): both the
    head and sequence splits are contiguous, so regrouping between the
    forward's and a backward kernel's (hg, block_q) is a plain reshape."""
    s = lse.shape[3] * lse.shape[4]
    return lse.reshape(b, h // hg, hg, s // block_q, block_q)


def _fold_rows(x, b, h, hg, block_q):
    """(b, s, h) f32 row statistic -> the kernels' (b, n_hg, hg, nq, bq)."""
    s = x.shape[1]
    return jnp.moveaxis(x, -1, 1).reshape(b, h // hg, hg, s // block_q,
                                          block_q)


def _bwd_dq_call(q3, k3, v3, do3, lse, delta, causal, scale, hg, d, spec,
                 interpret):
    """The dq pallas_call of the split backward — also the autotuner's
    flash_bwd_dq runner entry."""
    variant, block_q, block_k = spec
    feats = variant_features(variant, _BWD_FEATURES)
    b, s, hd = q3.shape
    sk = k3.shape[1]
    h = hd // d
    nq = s // block_q
    nk = sk // block_k
    hgd = hg * d
    lse5 = _fold_lse(lse, b, h, hg, block_q)
    delta5 = _fold_rows(delta, b, h, hg, block_q)
    row_spec = pl.BlockSpec((1, 1, hg, nq, block_q),
                            lambda bi, g, i, j: (bi, g, 0, 0, 0))
    q_spec_qout = pl.BlockSpec((1, block_q, hgd),
                               lambda bi, g, i, j: (bi, i, g))
    kv_spec_qout = pl.BlockSpec((1, block_k, hgd),
                                lambda bi, g, i, j: (bi, j, g))
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                          hg=hg, d=d, nk=nk,
                          bf16chain="bf16chain" in feats,
                          iotafree="iotafree" in feats),
        grid=(b, h // hg, nq, nk),
        in_specs=[q_spec_qout, kv_spec_qout, kv_spec_qout, q_spec_qout,
                  row_spec, row_spec],
        out_specs=q_spec_qout,
        out_shape=_sds((b, s, hd), q3.dtype, q3),
        scratch_shapes=[pltpu.VMEM((block_q, hgd), jnp.float32)],
        compiler_params=_SEQ2,
        name="flash_bwd_dq",
        interpret=interpret,
    )(q3, k3, v3, do3, lse5, delta5)


def _bwd_dkv_call(q3, k3, v3, do3, lse, delta, causal, scale, hg, d, spec,
                  interpret):
    """The dk/dv pallas_call of the split backward — also the autotuner's
    flash_bwd_dkv runner entry."""
    variant, block_q, block_k = spec
    feats = variant_features(variant, _BWD_FEATURES)
    b, s, hd = q3.shape
    sk = k3.shape[1]
    h = hd // d
    nq = s // block_q
    nk = sk // block_k
    hgd = hg * d
    lse5 = _fold_lse(lse, b, h, hg, block_q)
    delta5 = _fold_rows(delta, b, h, hg, block_q)
    row_spec = pl.BlockSpec((1, 1, hg, nq, block_q),
                            lambda bi, g, i, j: (bi, g, 0, 0, 0))
    q_spec_kout = pl.BlockSpec((1, block_q, hgd),
                               lambda bi, g, i, j: (bi, j, g))
    kv_spec_kout = pl.BlockSpec((1, block_k, hgd),
                                lambda bi, g, i, j: (bi, i, g))
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                          hg=hg, d=d, nq=nq,
                          bf16chain="bf16chain" in feats,
                          iotafree="iotafree" in feats),
        grid=(b, h // hg, nk, nq),
        in_specs=[q_spec_kout, kv_spec_kout, kv_spec_kout, q_spec_kout,
                  row_spec, row_spec],
        out_specs=[kv_spec_kout, kv_spec_kout],
        out_shape=[_sds((b, sk, hd), k3.dtype, k3),
                   _sds((b, sk, hd), v3.dtype, v3)],
        scratch_shapes=[pltpu.VMEM((block_k, hgd), jnp.float32),
                        pltpu.VMEM((block_k, hgd), jnp.float32)],
        compiler_params=_SEQ2,
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q3, k3, v3, do3, lse5, delta5)


def _bwd_merged_call(q3, k3, v3, do3, lse, delta, causal, scale, hg, d,
                     spec, interpret):
    """The merged dQ/dK/dV pallas_call — the autotuner's flash_bwd entry."""
    variant, block_q, block_k = spec
    feats = variant_features(variant, _BWD_FEATURES)
    b, s, hd = q3.shape
    sk = k3.shape[1]
    h = hd // d
    nq = s // block_q
    nk = sk // block_k
    hgd = hg * d
    lse5 = _fold_lse(lse, b, h, hg, block_q)
    delta5 = _fold_rows(delta, b, h, hg, block_q)
    q_spec = pl.BlockSpec((1, block_q, hgd), lambda bi, g, i, j: (bi, j, g))
    kv_spec = pl.BlockSpec((1, block_k, hgd), lambda bi, g, i, j: (bi, i, g))
    row_spec = pl.BlockSpec((1, 1, hg, nq, block_q),
                            lambda bi, g, i, j: (bi, g, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, scale=scale,
                          hg=hg, d=d, nq=nq, nk=nk,
                          bf16chain="bf16chain" in feats,
                          iotafree="iotafree" in feats),
        grid=(b, h // hg, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[
            # dq: whole-sequence block, revisited; written at the last step
            pl.BlockSpec((1, s, hgd), lambda bi, g, i, j: (bi, 0, g)),
            kv_spec,
            kv_spec,
        ],
        out_shape=[
            _sds((b, s, hd), q3.dtype, q3),
            _sds((b, sk, hd), k3.dtype, k3),
            _sds((b, sk, hd), v3.dtype, v3),
        ],
        scratch_shapes=[
            pltpu.VMEM((s, hgd), jnp.float32),
            pltpu.VMEM((block_k, hgd), jnp.float32),
            pltpu.VMEM((block_k, hgd), jnp.float32),
        ],
        compiler_params=_SEQ2,
        name="flash_bwd",
        interpret=interpret,
    )(q3, k3, v3, do3, lse5, delta5)


def _flash_bwd(q3, k3, v3, o3, lse, do3, causal, scale, d, interpret, spec,
               dlse=None):
    # dlse: optional (b, s, h) f32 cotangent of a base-e lse OUTPUT
    # (flash_attention_bshd_with_lse): it folds into the kernels as
    # delta - dlse — dS_ij = P_ij (dP_ij - delta_i + dlse_i), so the
    # existing kernels run unchanged.
    # spec: ("merged", variant, block_q, block_k, hg) or
    #       ("split", (variant, bq, bk), (variant, bq, bk), hg) — decided
    # by the wrapper (default: merged while the full-seq dq scratch fits).
    with x64_scope(False):
        b, s, hd = q3.shape
        h = hd // d
        # delta = rowsum(dO * O) per head — cheap, fused by XLA; folded to
        # the kernels' (b, n_hg, hg, nq, bq) row layout per call
        delta = jnp.sum(
            do3.reshape(b, s, h, d).astype(jnp.float32) *
            o3.reshape(b, s, h, d).astype(jnp.float32), axis=-1)  # (b,s,h)
        if dlse is not None:
            delta = delta - dlse.astype(jnp.float32)
        if spec[0] == "split":
            _, dq_spec, dkv_spec, hg = spec
            dq = _bwd_dq_call(q3, k3, v3, do3, lse, delta, causal, scale,
                              hg, d, dq_spec, interpret)
            dk, dv = _bwd_dkv_call(q3, k3, v3, do3, lse, delta, causal,
                                   scale, hg, d, dkv_spec, interpret)
            return dq, dk, dv
        _, variant, block_q, block_k, hg = spec
        return _bwd_merged_call(q3, k3, v3, do3, lse, delta, causal, scale,
                                hg, d, (variant, block_q, block_k),
                                interpret)


# ---------------------------------------------------------------------------
# reference + custom_vjp wiring
# ---------------------------------------------------------------------------

def _reference_bhsd(q, k, v, causal, scale):
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q3, k3, v3, causal, scale, d, interpret, fwd_spec, bwd_spec):
    # fwd_spec: (variant, block_q, block_k, hg) — the forward and backward
    # tune independently (the backward's full-sequence dq scratch binds its
    # head group; the forward can amortize more heads per grid cell)
    out, _ = _flash_fwd(q3, k3, v3, causal, scale, d, interpret, fwd_spec)
    return out


def _flash_vjp_fwd(q3, k3, v3, causal, scale, d, interpret, fwd_spec,
                   bwd_spec):
    out, lse = _flash_fwd(q3, k3, v3, causal, scale, d, interpret, fwd_spec)
    return out, (q3, k3, v3, out, lse)


def _flash_vjp_bwd(causal, scale, d, interpret, fwd_spec, bwd_spec, res, g):
    q3, k3, v3, out, lse = res
    # the backward regroups the folded lse rows itself (plain reshape —
    # both the head and q-block splits are contiguous)
    return _flash_bwd(q3, k3, v3, out, lse, g, causal, scale, d, interpret,
                      bwd_spec)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _prep_blocks(s, sk, causal, block_q, block_k, what):
    """Shared block policy of the public BSHD wrappers: shrink to the
    largest divisible power-of-two blocks (>=128), cap block_k at block_q
    under causal (the band split needs block_q %% block_k == 0), and raise
    on ragged tails."""
    block_q = min(block_q, s)
    block_k = min(block_k, sk)
    while block_q > 128 and s % block_q:
        block_q //= 2
    while block_k > 128 and sk % block_k:
        block_k //= 2
    if causal and block_k > block_q:
        block_k = block_q
    if s % block_q or sk % block_k:
        raise ValueError(
            "%s: seq lengths (%d, %d) must be divisible by block sizes "
            "(%d, %d) — ragged tails would be silently dropped; use the "
            "XLA path (kernels.flash_attention.supported() gates this)"
            % (what, s, sk, block_q, block_k))
    return block_q, block_k


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q3, k3, v3, causal, scale, d, interpret, fwd_spec, bwd_spec):
    out, lse2 = _flash_fwd(q3, k3, v3, causal, scale, d, interpret,
                           fwd_spec)
    return out, lse2


def _flash_lse_vjp_fwd(q3, k3, v3, causal, scale, d, interpret, fwd_spec,
                       bwd_spec):
    out, lse2 = _flash_fwd(q3, k3, v3, causal, scale, d, interpret,
                           fwd_spec)
    return (out, lse2), (q3, k3, v3, out, lse2)


def _flash_lse_vjp_bwd(causal, scale, d, interpret, fwd_spec, bwd_spec,
                       res, g):
    q3, k3, v3, out, lse2 = res
    dout, dlse2 = g
    b, s, hd = q3.shape
    h = hd // d
    # unfold the (b, n_hg, hg, nq, bq) base-2 lse cotangent to (b, s, h)
    # base-e: lse2 = lse_e * log2e, so dlse_e = dlse2 * log2e
    dlse = jnp.moveaxis(
        dlse2.reshape(b, h, s), 1, -1) * jnp.float32(_LOG2E)
    return _flash_bwd(q3, k3, v3, out, lse2, dout, causal, scale, d,
                      interpret, bwd_spec, dlse=dlse)


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


# ---------------------------------------------------------------------------
# autotune wiring: keys, spec resolution, candidates, runners
# ---------------------------------------------------------------------------

def autotune_key(b, s, sk, h, d, dtype, causal):
    from . import autotune as at
    return {"b": int(b), "s": int(s), "sk": int(sk), "h": int(h),
            "d": int(d), "dtype": str(jnp.dtype(dtype)),
            "causal": bool(causal), "platform": at.platform()}


def _valid_blocks(bq, bk, s, sk, causal):
    if not (isinstance(bq, int) and isinstance(bk, int)):
        return False
    if bq < 128 or bk < 128 or s % bq or sk % bk:
        return False
    if causal and (bk > bq or bq % bk):
        return False
    return True


def _valid_hg(hg, h, d):
    return isinstance(hg, int) and hg >= 1 and h % hg == 0 and \
        ((hg * d) % 128 == 0 or hg == h)


def _sane_fwd_spec(cand, s, sk, h, d, causal, default):
    """Validate a resolved/pinned flash_fwd candidate against the kernel's
    divisibility and alignment constraints; anything off falls back to the
    hand-tuned default (cache entries and pins are user input)."""
    cfg = cand.get("config", {})
    bq, bk, hg = cfg.get("block_q"), cfg.get("block_k"), cfg.get("hg")
    try:
        variant_features(cand.get("variant", "base"), _FWD_FEATURES)
    except ValueError:
        return ("base",) + default
    if not (_valid_blocks(bq, bk, s, sk, causal) and _valid_hg(hg, h, d)):
        return ("base",) + default
    return (cand["variant"], bq, bk, hg)


def _sane_bwd_blocks(cand, s, sk, causal, default):
    cfg = cand.get("config", {})
    bq, bk = cfg.get("block_q"), cfg.get("block_k")
    try:
        variant_features(cand.get("variant", "base"), _BWD_FEATURES)
    except ValueError:
        return ("base",) + default
    if not _valid_blocks(bq, bk, s, sk, causal):
        return ("base",) + default
    return (cand["variant"], bq, bk)


def _sane_bwd_merged(cand, s, sk, h, d, causal, default):
    cfg = cand.get("config", {})
    hg = cfg.get("hg")
    variant, bq, bk = _sane_bwd_blocks(cand, s, sk, causal, default[:2])
    if not _valid_hg(hg, h, d) or \
            max(s, sk) * hg * d * 4 > _DQ_SCRATCH_BUDGET:
        return ("merged", "base") + default
    return ("merged", variant, bq, bk, hg)


def _resolve_specs(b, s, sk, h, d, dtype, causal, block_q, block_k, hg_f,
                   hg_b, variant=None, tie_groups=False,
                   use_autotune=True):
    """(fwd_spec, bwd_spec) for one call: an explicit ``variant`` or
    caller-pinned block sizes (``use_autotune=False``) bypass the autotuner
    entirely (the A/B and parity-test entry); otherwise the specs resolve
    through autotune.resolve() with the hand-tuned values as the registered
    defaults — identical programs until tuning runs."""
    split = max(s, sk) * hg_b * d * 4 > _DQ_SCRATCH_BUDGET
    if variant is not None or not use_autotune:
        variant = variant or "base"
        fv = canon_variant(variant_features(variant, _FWD_FEATURES))
        bv = bwd_variant_of(variant)
        fwd_spec = (fv, block_q, block_k, hg_f)
        bwd_spec = (("split", (bv, block_q, block_k),
                     (bv, block_q, block_k), hg_b) if split
                    else ("merged", bv, block_q, block_k, hg_b))
        return fwd_spec, bwd_spec
    from . import autotune as at
    key = autotune_key(b, s, sk, h, d, dtype, causal)
    fwd_spec = _sane_fwd_spec(at.resolve("flash_fwd", key), s, sk, h, d,
                              causal, (block_q, block_k, hg_f))
    if split:
        bwd_spec = ("split",
                    _sane_bwd_blocks(at.resolve("flash_bwd_dq", key),
                                     s, sk, causal, (block_q, block_k)),
                    _sane_bwd_blocks(at.resolve("flash_bwd_dkv", key),
                                     s, sk, causal, (block_q, block_k)),
                    hg_b)
    else:
        bwd_spec = _sane_bwd_merged(at.resolve("flash_bwd", key),
                                    s, sk, h, d, causal,
                                    (block_q, block_k, hg_b))
    if tie_groups:
        # one group for both directions: the lse OUTPUT layout must match
        # what the caller-visible (b, s, h) unfold assumes alongside the
        # backward's consumption (flash_attention_bshd_with_lse).  A tuned
        # fwd winner with a DIFFERENT head group is discarded for the
        # hand-tuned default rather than silently re-grouped — the
        # (variant, blocks, hg) combination after a re-group was never
        # timed, and alternate-hg candidates differ ONLY by hg.
        hg = bwd_spec[4] if bwd_spec[0] == "merged" else bwd_spec[3]
        if fwd_spec[3] != hg:
            fwd_spec = ("base", block_q, block_k, hg)
    return fwd_spec, bwd_spec


_CAND_FWD_VARIANTS = ("iotafree", "bf16chain", "bf16chain+iotafree")
_CAND_FWD_RESIDENT = ("parq", "iotafree+parq")
_CAND_FWD_PIPELINED = ("pipelined", "iotafree+pipelined")
_CAND_BWD_VARIANTS = ("iotafree", "bf16chain", "bf16chain+iotafree")


def _candidate_blocks(s, sk, causal, bq0, bk0):
    pairs = [(bq0, bk0)]
    for bq in (256, 512, 1024):
        for bk in (128, 256, 512):
            if bq > s or bk > sk or s % bq or sk % bk:
                continue
            if causal and (bk > bq or bq % bk):
                continue
            if (bq, bk) not in pairs:
                pairs.append((bq, bk))
    return pairs[:6]


def _default_cfg(key):
    s, sk, h, d, causal = (key[k] for k in ("s", "sk", "h", "d", "causal"))
    hg_b = _pick_head_group(h, d, max(s, sk))
    hg_f = _pick_fwd_head_group(h, d, max(s, sk), hg_b)
    bq0, bk0 = _prep_blocks(s, sk, causal, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
                            "autotune")
    return bq0, bk0, hg_f, hg_b


def _fwd_candidates(key):
    s, sk, h, d, causal = (key[k] for k in ("s", "sk", "h", "d", "causal"))
    bq0, bk0, hg_f, hg_b = _default_cfg(key)
    cands = [{"variant": "base",
              "config": {"block_q": bq0, "block_k": bk0, "hg": hg_f}}]
    variants = list(_CAND_FWD_VARIANTS) + list(_CAND_FWD_PIPELINED)
    if _kv_fits_resident(sk, hg_f * d):
        variants += list(_CAND_FWD_RESIDENT)
    for bq, bk in _candidate_blocks(s, sk, causal, bq0, bk0):
        for v in (["base"] if (bq, bk) != (bq0, bk0) else []) + variants:
            # the pipelined kernel carries every head's (m, l, acc) through
            # its fori loop on top of the K/V double buffers: at the
            # forward's wide group (hg*d = 512) Mosaic's scoped VMEM runs
            # out at the standard key (16.07M of 16M), at the backward's
            # group (hg*d <= 256) every block pair below compiles
            hg = hg_b if "pipelined" in v else hg_f
            cand = {"variant": v,
                    "config": {"block_q": bq, "block_k": bk, "hg": hg}}
            if cand not in cands:
                cands.append(cand)
    # alternate head groups for the base variant only (bounds the grid)
    for hg in _aligned_groups(h, d):
        if hg != hg_f and hg * d <= 512:
            cands.append({"variant": "base",
                          "config": {"block_q": bq0, "block_k": bk0,
                                     "hg": hg}})
    return cands


def _bwd_candidates_merged(key):
    s, sk, h, d, causal = (key[k] for k in ("s", "sk", "h", "d", "causal"))
    bq0, bk0, hg_f, hg_b = _default_cfg(key)
    cands = [{"variant": "base",
              "config": {"block_q": bq0, "block_k": bk0, "hg": hg_b}}]
    for bq, bk in _candidate_blocks(s, sk, causal, bq0, bk0):
        for v in (["base"] if (bq, bk) != (bq0, bk0) else []) + \
                list(_CAND_BWD_VARIANTS):
            cand = {"variant": v,
                    "config": {"block_q": bq, "block_k": bk, "hg": hg_b}}
            if cand not in cands:
                cands.append(cand)
    for hg in _aligned_groups(h, d):
        if hg != hg_b and hg * d <= 256 and \
                max(s, sk) * hg * d * 4 <= _DQ_SCRATCH_BUDGET:
            cands.append({"variant": "base",
                          "config": {"block_q": bq0, "block_k": bk0,
                                     "hg": hg}})
    return cands


def _bwd_candidates_split(key):
    s, sk, causal = key["s"], key["sk"], key["causal"]
    bq0, bk0, _, _ = _default_cfg(key)
    cands = [{"variant": "base", "config": {"block_q": bq0,
                                            "block_k": bk0}}]
    for bq, bk in _candidate_blocks(s, sk, causal, bq0, bk0):
        for v in (["base"] if (bq, bk) != (bq0, bk0) else []) + \
                list(_CAND_BWD_VARIANTS):
            cand = {"variant": v, "config": {"block_q": bq, "block_k": bk}}
            if cand not in cands:
                cands.append(cand)
    return cands


#: per-key synthetic operand cache shared by the runner factories (the
#: backward runners also reuse the default-forward (out, lse) residuals)
_RUNNER_DATA: dict = {}


def _runner_data(key):
    from . import autotune as at
    ks = at.key_str(key)
    hit = _RUNNER_DATA.get(ks)
    if hit is not None:
        return hit
    b, s, sk, h, d = (key[k] for k in ("b", "s", "sk", "h", "d"))
    causal = key["causal"]
    dtype = jnp.dtype(key["dtype"])
    interpret = key["platform"] != "tpu"
    rng = np.random.RandomState(0)
    with x64_scope(False):
        q3 = jnp.asarray(rng.standard_normal((b, s, h * d)), dtype)
        k3 = jnp.asarray(rng.standard_normal((b, sk, h * d)), dtype)
        v3 = jnp.asarray(rng.standard_normal((b, sk, h * d)), dtype)
        do3 = jnp.asarray(rng.standard_normal((b, s, h * d)), dtype)
        bq0, bk0, hg_f, hg_b = _default_cfg(key)
        scale = 1.0 / d ** 0.5
        out, lse = jax.jit(lambda a, bb, c: _flash_fwd(
            a, bb, c, causal, scale, d, interpret,
            ("base", bq0, bk0, hg_b)))(q3, k3, v3)
        delta = jnp.sum(
            do3.reshape(b, s, h, d).astype(jnp.float32) *
            out.reshape(b, s, h, d).astype(jnp.float32), axis=-1)
        jax.block_until_ready((out, lse, delta))
    data = {"q3": q3, "k3": k3, "v3": v3, "do3": do3, "out": out,
            "lse": lse, "delta": delta, "scale": scale, "hg_b": hg_b,
            "interpret": interpret}
    _RUNNER_DATA[ks] = data
    return data


def _fwd_runner(cand, key):
    data = _runner_data(key)
    cfg = cand["config"]
    spec = (cand["variant"], cfg["block_q"], cfg["block_k"], cfg["hg"])
    causal, d = key["causal"], key["d"]
    fn = jax.jit(lambda q, k, v: _flash_fwd(
        q, k, v, causal, data["scale"], d, data["interpret"], spec))

    def run():
        jax.block_until_ready(fn(data["q3"], data["k3"], data["v3"]))
    return run


def _bwd_runner(which):
    def make(cand, key):
        data = _runner_data(key)
        cfg = cand["config"]
        causal, d = key["causal"], key["d"]
        hg = cfg.get("hg", data["hg_b"])
        spec = (cand["variant"], cfg["block_q"], cfg["block_k"])
        call = {"merged": _bwd_merged_call, "dq": _bwd_dq_call,
                "dkv": _bwd_dkv_call}[which]

        def timed(q, k, v, do, lse, delta):
            # same x64-off trace scope as the production entry
            # (_flash_bwd) — under the global x64 mode the candidate
            # would otherwise lower a different (or unlowerable) program
            # than the one production runs
            with x64_scope(False):
                return call(q, k, v, do, lse, delta, causal,
                            data["scale"], hg, d, spec,
                            data["interpret"])
        fn = jax.jit(timed)

        def run():
            jax.block_until_ready(fn(
                data["q3"], data["k3"], data["v3"], data["do3"],
                data["lse"], data["delta"]))
        return run
    return make


def _runner_cleanup(key):
    from . import autotune as at
    _RUNNER_DATA.pop(at.key_str(key), None)


# -- abstract traceables (TPU504 / trace-tier audit) -------------------------
# Data-free builders of each candidate's program: args are
# ShapeDtypeStructs, so make_jaxpr prices the BlockSpec working set
# without touching a device — the autotuner's pre-compile VMEM gate and
# the analysis registry's per-variant kernel programs both come from
# these.  ``interpret=False`` builds the Mosaic program instead, for
# ahead-of-time compiles against a TPU topology.

def _fwd_traceable(cand, key, interpret=True):
    b, s, sk, h, d = (key[k] for k in ("b", "s", "sk", "h", "d"))
    causal, dtype = key["causal"], jnp.dtype(key["dtype"])
    cfg = cand["config"]
    spec = (cand["variant"], cfg["block_q"], cfg["block_k"], cfg["hg"])
    scale = 1.0 / d ** 0.5

    def fn(q, k, v):
        return _flash_fwd(q, k, v, causal, scale, d, interpret, spec)
    sds = jax.ShapeDtypeStruct
    return fn, (sds((b, s, h * d), dtype), sds((b, sk, h * d), dtype),
                sds((b, sk, h * d), dtype))


def _bwd_traceable(which):
    def make(cand, key, interpret=True):
        b, s, sk, h, d = (key[k] for k in ("b", "s", "sk", "h", "d"))
        causal, dtype = key["causal"], jnp.dtype(key["dtype"])
        cfg = cand["config"]
        bq0, _bk0, _hg_f, hg_b = _default_cfg(key)
        hg = cfg.get("hg", hg_b)
        spec = (cand["variant"], cfg["block_q"], cfg["block_k"])
        scale = 1.0 / d ** 0.5
        call = {"merged": _bwd_merged_call, "dq": _bwd_dq_call,
                "dkv": _bwd_dkv_call}[which]

        def fn(q, k, v, do, lse, delta):
            with x64_scope(False):
                return call(q, k, v, do, lse, delta, causal, scale, hg, d,
                            spec, interpret)
        sds = jax.ShapeDtypeStruct
        # lse/delta in the layout the default forward produces (what the
        # production bwd — and the timed runner — actually receives)
        return fn, (sds((b, s, h * d), dtype), sds((b, sk, h * d), dtype),
                    sds((b, sk, h * d), dtype), sds((b, s, h * d), dtype),
                    sds((b, h // hg_b, hg_b, s // bq0, bq0), jnp.float32),
                    sds((b, s, h), jnp.float32))
    return make


def _register_families():
    from . import autotune as at
    at.register_family("flash_fwd", _fwd_candidates, _fwd_runner,
                       cleanup=_runner_cleanup, traceable=_fwd_traceable)
    at.register_family("flash_bwd", _bwd_candidates_merged,
                       _bwd_runner("merged"), cleanup=_runner_cleanup,
                       traceable=_bwd_traceable("merged"))
    at.register_family("flash_bwd_dq", _bwd_candidates_split,
                       _bwd_runner("dq"), cleanup=_runner_cleanup,
                       traceable=_bwd_traceable("dq"))
    at.register_family("flash_bwd_dkv", _bwd_candidates_split,
                       _bwd_runner("dkv"), cleanup=_runner_cleanup,
                       traceable=_bwd_traceable("dkv"))


_register_families()


# ---------------------------------------------------------------------------
# public BSHD wrappers
# ---------------------------------------------------------------------------

def flash_attention_bshd_with_lse(q, k, v, causal=False, scale=None,
                                  block_q=DEFAULT_BLOCK_Q,
                                  block_k=DEFAULT_BLOCK_K,
                                  interpret=False, variant=None):
    """Like :func:`flash_attention_bshd_native` but ALSO returns the
    row logsumexp in BASE E, shape (B, S, H) — and stays differentiable
    when the caller consumes both (the lse cotangent folds into the
    backward kernels as ``delta - dlse``).  This is the building block
    the ring-attention inner needs (r4 verdict #3): per-shard
    (out, lse) pairs combine exactly like global attention."""
    b, s, h, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    hg_b = _pick_head_group(h, d, max(s, sk))
    default_blocks = (block_q, block_k) == (DEFAULT_BLOCK_Q,
                                            DEFAULT_BLOCK_K)
    block_q, block_k = _prep_blocks(s, sk, causal, block_q, block_k,
                                    "flash_attention_with_lse")
    fwd_spec, bwd_spec = _resolve_specs(
        b, s, sk, h, d, q.dtype, causal, block_q, block_k, hg_b, hg_b,
        variant=variant, tie_groups=True, use_autotune=default_blocks)
    q3 = q.reshape(b, s, h * d)
    k3 = k.reshape(b, sk, h * d)
    v3 = v.reshape(b, sk, h * d)
    out, lse2 = _flash_lse(q3, k3, v3, causal, float(scale), d, interpret,
                           fwd_spec, bwd_spec)
    # (b, n_hg, hg, nq, bq) base-2 -> (b, s, h) base-e
    lse = jnp.moveaxis(lse2.reshape(b, h, s), 1, -1) / jnp.float32(_LOG2E)
    return out.reshape(b, s, h, d), lse


def flash_attention_bshd_native(q, k, v, causal=False, scale=None,
                                block_q=DEFAULT_BLOCK_Q,
                                block_k=DEFAULT_BLOCK_K, interpret=False,
                                variant=None):
    """q,k,v: (B, S, H, D) — the model's native layout; no transposes.
    ``variant`` pins a kernel variant (e.g. "bf16chain+iotafree") for both
    directions, bypassing the autotuner; None resolves through it."""
    b, s, h, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    hg_b = _pick_head_group(h, d, max(s, sk))
    hg_f = _pick_fwd_head_group(h, d, max(s, sk), hg_b)
    default_blocks = (block_q, block_k) == (DEFAULT_BLOCK_Q,
                                            DEFAULT_BLOCK_K)
    block_q, block_k = _prep_blocks(s, sk, causal, block_q, block_k,
                                    "flash_attention")
    fwd_spec, bwd_spec = _resolve_specs(
        b, s, sk, h, d, q.dtype, causal, block_q, block_k, hg_f, hg_b,
        variant=variant, use_autotune=default_blocks)
    q3 = q.reshape(b, s, h * d)
    k3 = k.reshape(b, sk, h * d)
    v3 = v.reshape(b, sk, h * d)
    out = _flash(q3, k3, v3, causal, float(scale), d, interpret, fwd_spec,
                 bwd_spec)
    return out.reshape(b, s, h, d)


def flash_attention_bhsd(q, k, v, causal=False, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         interpret=False, variant=None):
    """q,k,v: (B, H, S, D) — compat wrapper over the native BSHD kernel
    (introduces two transposes; the model path uses BSHD directly)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bshd_native(qt, kt, vt, causal=causal, scale=scale,
                                      block_q=block_q, block_k=block_k,
                                      interpret=interpret, variant=variant)
    return jnp.swapaxes(out, 1, 2)
