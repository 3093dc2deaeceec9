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
head-major kernel forces around every call (history, retired set-up:
about 9 ms a step of HBM copies on GPT-2 345M).

Packed operands (PR 32).  The three operands need not be three arrays: a
fused projection's (B, S, 3*H*D) output in ``[q | k | v]`` column order is
passed three times and each part is picked by its BlockSpec's index map —
column block ``g`` for q, ``n + g`` for k, ``2n + g`` for v, ``n = H*D /
(hg*D)`` at the kernel's own head group (``_kv_parts``, ``_kv_specs``;
``flash_attention_packed_native``).  A Mosaic call cannot take a slice as
an operand, so slicing first is a three-output pass over the buffer a
layer (201 MB at 16 x 1,024 x 16 heads of 64); in place, the DMA moves the
same tiles from a row three times as wide and no kernel body changes.  The
backward still writes dq, dk and dv as three arrays, and the packed
``custom_vjp`` returns their sum of pads: XLA fuses that into the
projection's gradient GEMMs (a concatenate would be three update-slice
passes).

Forward: grid (B, n_hg, nq); the whole K/V sequence stays VMEM-resident and
is scanned with a fori loop over the fully-visible k blocks (no mask
arithmetic), the causal band behind it.  Sequences whose K/V do not fit
take the grid-streamed forward; ``pipelined`` streams K/V itself.

The causal band (PR 26).  A diagonal block is never computed whole: it is
cut into static ``t x t`` sub-tiles (``_band_tile``: 256, or what divides
the block) and walked with Python-static loops and static ref slices
(``_live_tiles`` is the one definition of which tiles are live,
``_band_runs`` the walk every causal kernel shares).  Sub-tiles strictly
above the diagonal emit nothing; each t-row sub-block takes its visible
prefix and its own diagonal tile in ONE step, the triangle applied to the
step's last t columns only (``_mask_tail``).  Row and column offsets
cancel on the diagonal, so that triangle is one constant of the kernel:
no mask depends on ``program_id``.  When block_q == block_k == t the walk
is one masked step, the band as it was.  Non-causal calls and fully
visible blocks lower to the same kernel body as before.  What the chip
said (v5e, 16 x 1,024 x 16 heads of 64, PERF.md section 6): steps cost
beside elements — t = 256 beats t = 128 though it computes more — the
forward is a third shorter, the merged backward unchanged: what it paid
was around its cells (PR 30: the resident backward).

Backward: three rungs, the residency chosen from the shape alone
(``_bwd_plan``; no switch).  The first two produce dQ, dK and dV from ONE
recompute of the logits and dP (the textbook two-kernel FlashAttention-2
split, the third rung, recomputes both twice):

- ``resident`` (PR 30) where a (batch, head group)'s whole backward fits
  VMEM and its walk is short (``_resident_bwd_fits``: s up to 2,048 at the
  default blocks).  Grid (B, n_hg), both parallel: q, k, v, dO and O and
  the lse rows come in as whole-sequence blocks, each crossing HBM once
  while the cell before computes.  It takes O, not delta: ``delta =
  rowsum(dO * O)`` is formed in the cell, in f32, from rows it already
  holds, so no caller builds the f32 product (XLA had made it a second
  output of the output projection's input-gradient GEMM, copied it
  transposed and reduced it: 64 MB written and read again a layer at the
  benchmark's shape).  The walk over block pairs is Python-static
  (``_cell_runs``, the static twin of ``_causal_cells``): pairs strictly
  in the future are absent, fully visible pairs run whole, band pairs by
  their live runs under the constant triangle; dq / dk / dv row ranges are
  sums of a handful of block contributions held as values and stored
  once, scaled and cast in the store — no full-sequence scratch, nothing
  zeroed, nothing read back.  The lse cotangent of
  ``flash_attention_bshd_with_lse`` rides beside the lse rows and is
  subtracted from delta in the cell.  What the chip said (v5e, PERF.md
  section 6, PR 30): in GPT-2 345M's step at 16 x 1,024 the kernel takes
  31.6 ms where the merged one took 40.5 (1.32 ms a layer against about
  1.1 of half-filled MXU passes at head size 64), delta's passes in XLA
  (6.6 + 2.4 ms) are gone, 54,968 -> 58,618 tokens/s; at (2 x 2,048, 16
  heads of 128) a call takes 0.65 ms against 0.86; the smallest
  lane-aligned head group is as fast as twice it.
- ``merged`` beyond that, while a full-sequence f32 dq scratch fits
  (``_DQ_SCRATCH_BUDGET``): grid (B, n_hg, nk, nq), both inner dims
  sequential; dK/dV accumulate per key block in scratch (reset at qi==0),
  dQ across the whole (nk, nq) sweep in the full-sequence scratch written
  at the final step; ``_causal_cells`` classifies a grid cell at run time
  (strictly-future cells do no work and still stream the prefetch).  It is
  handed delta, built in XLA.
- ``split`` beyond that: the dq and dk/dv kernels, O(block) VMEM, same
  walk, also handed delta.

(History, retired set-up: a fori-style backward, K/V outer and q scanned
inside, was slower, 47.6k against 49.6k tokens/s on the 345M bench.)

Variants: every kernel family is registered with the autotuner
(kernels/autotune.py) and the softmax/pipeline machinery is variant-
selectable — the hand-tuned configuration is the "base" variant and
the default, so nothing changes until tuning runs or a config is pinned:

- ``bf16chain``: the streaming-softmax elementwise chain (mask select,
  running max, exp2, p) runs in bf16 with the max/sum-exp2/correction
  STATISTICS still accumulated in f32, and p feeding the MXU in bf16
  without the separate f32->bf16 cast.  (The v5e's VPU has no bf16
  arithmetic to make it pay; it lowers the chain's precision.)
- ``iotafree``: absorbed by the sub-tiled band — it made the band mask
  one compare of a constant matrix against the block offset; the band
  mask is now a constant outright.  The name is still accepted (pins,
  caches, the autotuner's candidate lists) and selects nothing.
- ``parq`` (fwd, resident path): per-q-block lse output blocks instead of
  the revisited whole-sequence lse slice, which lets all three grid dims
  carry "parallel" dimension_semantics.
- ``pipelined`` (fwd): K/V stay in HBM (ANY memory space) and the kernel
  double-buffers block_k-sized chunks VMEM-ward with explicit async
  copies, overlapping the K/V fetch of block i+1 with the softmax chain of
  block i — the streamed forward's copy/compute overlap at sub-grid
  granularity.

All variants have interpret-mode parity tests vs the O(S^2) reference
(tests/test_flash_variants.py); tests/test_flash_tpu_compile.py compiles
the main path for a described v5e (Mosaic refuses what the interpreter
accepts).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.dtype import x64_scope


#: 512/512 won on the v5e over 1,024/512 and 512/256 (PERF.md section 6,
#: PR 30); ``block_q``/``block_k`` stay arguments and autotune configs
DEFAULT_BLOCK_Q = DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30
#: largest edge of the causal band's sub-tiles (_band_tile is the rule):
#: 256 against 128 measured on the v5e at (s, d) = (1,024, 64) and (2,048,
#: 128) — fewer, wider steps beat fewer score elements (PERF.md, PR 26)
_BAND_TILE = 256
# The streaming softmax runs in BASE 2: folding log2(e) into the logits
# scale turns every exp into the VPU's native exp2 (jnp.exp lowers to
# exp2 + a multiply per element, and the softmax exp over b*h*s^2 logits
# is the kernel's dominant VPU cost).  lse is therefore stored in base-2
# units; the backward consumes it with exp2 as well, and d/d(qk) keeps the
# plain base-e `scale` factor (dS = scale * P * (dP - delta) regardless).
_LOG2E = 1.4426950408889634

_SEQ2 = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"))

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


def _kv_parts(packed: bool, n: int):
    """Column-block offsets ``(k, v)`` of the key and value parts in the
    operand they are read from.  Packed, q, k and v are ONE ``(b, s,
    3*h*d)`` buffer in ``[q | k | v]`` column order (the fused projection's
    output as the GEMM wrote it), each part ``n`` blocks of ``hg*d``
    columns wide, passed to the call three times: the parts are told apart
    by their block index maps alone and no kernel body knows.  Each kernel
    has its own head group, hence its own ``n``."""
    return (n, 2 * n) if packed else (0, 0)


def _kv_specs(block, index_map, packed: bool, n: int):
    """The key's and the value's BlockSpec: ``index_map`` (a part's own,
    ``-> (batch, row block, column block)``) with the column block moved
    right by the part's offset (:func:`_kv_parts`; the same map where that
    is 0)."""
    def at(off):
        if not off:
            return index_map

        def shifted(*ids):
            bi, r, g = index_map(*ids)
            return bi, r, g + off
        return shifted
    return [pl.BlockSpec(block, at(off)) for off in _kv_parts(packed, n)]


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
# VMEM the RESIDENT backward may plan for (_resident_bwd_bytes: its eight
# whole-sequence blocks, double-buffered by the pipeline, plus one
# head-cell's temporaries) and the longest static walk it unrolls (nq * nk
# block pairs a head: 16 compile in 3-14 s, 64 took a minute).  Shapes
# beyond either take the merged or the split backward.  Found by compiling
# for a described v5e (tests/test_flash_tpu_compile.py): Mosaic asks
# within 4% of the plan — 6.3 MB at (s 1,024, hg*d 128), 11.9 MB at
# (2,048, 128) — and 21.6 MB for float32 operands at the largest shape
# admitted, hence the call's vmem_limit_bytes (the default scoped limit is
# 16 MiB of the v5e's 128).
_RESIDENT_BWD_BUDGET = 12 * 1024 * 1024
_RESIDENT_BWD_MAX_CELLS = 16
_RESIDENT_BWD_VMEM_LIMIT = 32 * 1024 * 1024


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


def _resident_bwd_bytes(s: int, sk: int, hgd: int, block_q: int,
                        block_k: int) -> int:
    """What a (batch, head group) cell of the resident backward plans to
    hold: q, dO, O, dq (s rows) and k, v, dk, dv (sk rows) as bf16
    whole-sequence blocks, double-buffered, and one head-cell's score-sized
    temporaries (logits, p and dP in f32, p and dS in the operand dtype)."""
    return 2 * 4 * (s + sk) * hgd * 2 + 16 * block_q * block_k


def _resident_bwd_fits(s, sk, hgd, causal, block_q, block_k) -> bool:
    """Whether the resident backward takes this shape at these blocks: the
    static walk covers it (a causal call is square — with sk > s whole key
    blocks would have no visible score and no store), it is short, and the
    working set is within the budget."""
    return _valid_blocks(block_q, block_k, s, sk, causal) and \
        (s == sk or not causal) and \
        (s // block_q) * (sk // block_k) <= _RESIDENT_BWD_MAX_CELLS and \
        _resident_bwd_bytes(s, sk, hgd, block_q, block_k) <= \
        _RESIDENT_BWD_BUDGET


def _resident_bwd_group(s, sk, h, d, causal, block_q, block_k):
    """Heads per cell of the resident backward at this shape, or None: the
    shape is the merged or the split backward's.  The SMALLEST lane-aligned
    group (hg*d = 128 where d divides it): the v5e ran it as fast as twice
    the group at both head sizes (1.461 against 1.475 ms a call at (16 x
    1,024, 16 x 64), 0.651 against 0.654 at (2 x 2,048, 16 x 128); PERF.md,
    PR 30) on half the working set and a third of the compile."""
    hg = _aligned_groups(h, d)[-1]
    if hg * d <= 256 and _resident_bwd_fits(s, sk, hg * d, causal, block_q,
                                            block_k):
        return hg
    return None


def _bwd_plan(s, sk, h, d, causal, block_q, block_k, hg_b):
    """``(path, hg)`` of one differentiated call, from its shape alone:
    ``resident`` where a (batch, head group)'s whole backward fits VMEM,
    ``merged`` while the full-sequence dq scratch does, ``split`` beyond."""
    hg = _resident_bwd_group(s, sk, h, d, causal, block_q, block_k)
    if hg is not None:
        return "resident", hg
    if max(s, sk) * hg_b * d * 4 <= _DQ_SCRATCH_BUDGET:
        return "merged", hg_b
    return "split", hg_b


def _pick_fwd_head_group(h: int, d: int, s: int, hg_b: int) -> int:
    """The forward has no full-sequence scratch, so it can afford a larger
    group (up to hg*d = 512) when the resident K/V still fits — fewer grid
    cells amortize per-cell overhead.  Falls back to the backward's group."""
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

def _band_tile(block_k: int) -> int:
    """Edge ``t`` of the square sub-tiles a causal band cell is cut into —
    THE one rule.  ``t`` divides ``block_k`` (and so ``block_q``: causal
    blocks have block_q % block_k == 0); a block no candidate divides
    stays whole (one masked tile, the band before it was sub-tiled).  Head
    size and dtype do not enter: 256 won at d = 64 and at d = 128."""
    for t in (_BAND_TILE, 128):
        if block_k % t == 0:
            return t
    return block_k


def _live_tiles(block_q: int, block_k: int, t: int, off: int):
    """``[(r, c, masked)]``: the ``t x t`` sub-tiles of a (block_q,
    block_k) causal cell that hold ANY visible score, where the cell's
    first row sits ``off = row0 - col0`` columns right of its first column
    (vis[i, j] = j <= i + off).  THE one definition of which tiles are
    live: tiles strictly above the diagonal are absent, tiles strictly
    below are unmasked, tiles the diagonal crosses are ``masked``.  With
    ``off`` a multiple of ``t`` (every caller's case) a masked tile's mask
    is the same lower triangle whatever the block or grid cell."""
    assert off % t == 0 and block_q % t == 0 and block_k % t == 0
    out = []
    for r in range(block_q // t):
        first_row, last_row = r * t + off, r * t + t - 1 + off
        for c in range(block_k // t):
            if c * t > last_row:
                continue                  # every column is in the future
            out.append((r, c, c * t + t - 1 > first_row))
    return out


def _band_runs(block_q: int, block_k: int, t: int, off: int):
    """:func:`_live_tiles` as ``[(rows, cols, masked)]`` static slices
    into the cell: the live tiles of one tile row merged into a single
    ``t x (n*t)`` rectangle — one wide matmul and one rescale a row, the
    step count being what the kernels pay for beside the elements —
    ``masked`` when its LAST ``t`` columns are the row's diagonal tile
    (:func:`_mask_tail` applies the triangle there)."""
    last = {}       # a row's tiles come in column order: keep the last
    for r, c, masked in _live_tiles(block_q, block_k, t, off):
        last[r] = (c, masked)
    return [(slice(r * t, (r + 1) * t), slice(0, (c + 1) * t), masked)
            for r, (c, masked) in last.items()]


def _mask_tail(x, vis, fill):
    """``x`` (R, W) with its last ``t`` columns under the (R, t) triangle
    ``vis``: hidden elements become ``fill``, the columns before the
    diagonal tile pass untouched (no mask arithmetic there)."""
    t = vis.shape[1]
    if x.shape[1] == t:
        return jnp.where(vis, x, fill)
    return jnp.concatenate(
        [x[:, :-t], jnp.where(vis, x[:, -t:], fill)], axis=1)


def _tri_vis(t: int):
    """The masked tile's visibility, ``col <= row`` on a ``t x t`` tile: a
    constant of the kernel (row and column offsets cancel on the
    diagonal), built from in-kernel iotas because Pallas rejects captured
    host constants."""
    return jax.lax.broadcasted_iota(jnp.int32, (t, t), 1) <= \
        jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)


def _causal_cells(compute, causal, qi, ki, block_q, block_k, t):
    """Run ``compute(rows, cols, vis)`` over what grid cell (qi, ki) must
    attend: the whole cell unmasked (non-causal, or strictly below the
    diagonal), nothing (strictly future), or — in one of the block_q //
    block_k band cells — each live run of sub-tiles, ``vis`` the constant
    triangle on the diagonal tiles and None elsewhere.  The band cell's
    offset is static per branch, so the walk is Python-static."""
    whole = (slice(0, block_q), slice(0, block_k))
    if not causal:
        compute(*whole, None)
        return
    ratio = block_q // block_k
    # < 0: fully visible; [0, ratio): the band; >= ratio: strictly future
    j = ki - jax.lax.mul(qi, _i32(ratio))
    pl.when(j < 0)(lambda: compute(*whole, None))
    for jj in range(ratio):
        def band(jj=jj):
            tri = _tri_vis(t)
            for rows, cols, masked in _band_runs(block_q, block_k, t,
                                                 -jj * block_k):
                compute(rows, cols, tri if masked else None)
        pl.when(j == jj)(band)


def score_elements(s: int, block_q: int, t: int):
    """(computed, causal) score elements a head of one causal call costs:
    what the forward kernel's block and sub-tile walk computes — whole
    blocks below the diagonal plus the live sub-tiles of each diagonal
    block — against the s*(s+1)/2 the mask needs."""
    nq = s // block_q
    live = len(_live_tiles(block_q, block_q, t, 0))
    return (nq * (nq - 1) // 2 * block_q * block_q + nq * live * t * t,
            s * (s + 1) // 2)


def _online_step(q, k, v, m, l, acc, vis, scale, bf16chain):
    """One streaming-softmax accumulation over a K/V block.

    (m, l, acc) are the running f32 statistics; ``vis`` is None (unmasked
    block) or the visibility mask of the block's last ``vis.shape[1]``
    columns (:func:`_mask_tail`).  bf16chain runs the
    elementwise chain (select, exp2, p) in bf16 with f32 statistics — p
    then feeds the MXU without a separate cast.
    """
    # bf16 x bf16 -> f32 is the MXU's native mode; upcasting operands
    # first quarters matmul throughput
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.float32(scale * _LOG2E)
    if bf16chain:
        lb = logits.astype(jnp.bfloat16)
        if vis is not None:
            lb = _mask_tail(lb, vis, jnp.bfloat16(_NEG_INF))
        new_m = jnp.maximum(m, jnp.max(lb, axis=-1).astype(jnp.float32))
        p = jnp.exp2(lb - new_m.astype(jnp.bfloat16)[:, None])
        psum = jnp.sum(p, axis=-1, dtype=jnp.float32)
    else:
        if vis is not None:
            logits = _mask_tail(logits, vis, jnp.float32(_NEG_INF))
        new_m = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp2(logits - new_m[:, None])
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
            p = _mask_tail(p, vis, jnp.bfloat16(0.0))
    else:
        p = jnp.exp2(logits - lse[:, None])
        if vis is not None:
            p = _mask_tail(p, vis, jnp.float32(0.0))
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

def _split_rows(m, l, acc, t):
    """Whole-block (m, l, acc) -> ``{first_row: (m, l, acc)}`` per t-row
    sub-block: the band advances each sub-block on its own."""
    return {r0: (m[r0:r0 + t], l[r0:r0 + t], acc[r0:r0 + t])
            for r0 in range(0, m.shape[0], t)}


def _band_steps(st, q_of, kv_of, runs, tri, scale, bf16chain):
    """Advance the per-sub-block states ``st`` over the live ``runs`` of a
    band cell; ``q_of(rows)`` / ``kv_of(cols)`` load the operands."""
    for rows, cols, masked in runs:
        k, v = kv_of(cols)
        st[rows.start] = _online_step(
            q_of(rows), k, v, *st[rows.start], tri if masked else None,
            scale, bf16chain)


def _fwd_finish(st, o_ref, sl, lse_ref, lse_idx):
    """Normalise the finished sub-block states of one head into its
    output rows and its lse row (base-2 units: m is already log2-scaled).
    The lse pieces leave in ONE store: Mosaic has no (1, t) store at a
    lane offset into a dynamically indexed row."""
    lse_rows = []
    for r0, (m, l, acc) in st.items():
        l_safe = jnp.maximum(l, jnp.float32(1e-30))
        o_ref[0, r0:r0 + m.shape[0], sl] = \
            (acc / l_safe[:, None]).astype(o_ref.dtype)
        lse_rows.append(
            (m + jnp.log(l_safe) * jnp.float32(_LOG2E))[None, :])
    lse_ref[lse_idx] = lse_rows[0] if len(lse_rows) == 1 else \
        jnp.concatenate(lse_rows, axis=1)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, scale, hg,
                d, block_k, tile, bf16chain=False, parq=False):
    # q/o: (1, BQ, HG*D); k/v: (1, S, HG*D) — the WHOLE sequence resident
    # in VMEM, scanned with a fori loop (faster than grid-streamed K/V
    # blocks at the bench shapes: the pipeline only added grid overhead);
    # lse: (1, 1, HG, NQ, BQ) — or per-q-block (1, 1, 1, HG, BQ) under parq
    # (q-block-major, so the block's last two dims are the whole (HG, BQ)
    # tile Mosaic requires; the wrapper swaps it back).
    block_q = q_ref.shape[1]
    s = k_ref.shape[1]
    qi = _pid(2)
    if causal:
        assert block_q % block_k == 0
        ratio = block_q // block_k
        band0 = jax.lax.mul(qi, _i32(block_q))
        tri = _tri_vis(tile)

    for hh in range(hg):
        sl = slice(hh * d, (hh + 1) * d)
        q = q_ref[0, :, sl]                                   # (BQ, D)

        def body(kb, carry):
            m, l, acc = carry
            start = jax.lax.mul(kb, _i32(block_k))
            k = k_ref[0, pl.ds(start, block_k), sl]
            v = v_ref[0, pl.ds(start, block_k), sl]
            return _online_step(q, k, v, m, l, acc, None, scale, bf16chain)

        init = (jnp.full((block_q,), jnp.float32(_NEG_INF), jnp.float32),
                jnp.zeros((block_q,), jnp.float32),
                jnp.zeros((block_q, d), jnp.float32))
        if causal:
            # the k blocks before the diagonal are fully visible: whole
            # block_q rows, no mask arithmetic.  The block_q // block_k k
            # blocks on it are the band: each t-row sub-block takes its
            # visible prefix of a band block and its own t x t tile under
            # the constant triangle in one step; the sub-tiles above the
            # diagonal are never computed
            num_full = jax.lax.mul(qi, _i32(ratio))
            st = _split_rows(*jax.lax.fori_loop(_i32(0), num_full, body,
                                                init), tile)
            for jj in range(ratio):
                _band_steps(
                    st, lambda rows: q_ref[0, rows, sl],
                    lambda cols: tuple(
                        ref[0, pl.ds(band0 + _i32(jj * block_k + cols.start),
                                     cols.stop - cols.start), sl]
                        for ref in (k_ref, v_ref)),
                    _band_runs(block_q, block_k, tile, -jj * block_k),
                    tri, scale, bf16chain)
        else:
            st = {0: jax.lax.fori_loop(_i32(0), _i32(s // block_k), body,
                                       init)}
        _fwd_finish(st, o_ref, sl, lse_ref,
                    (0, 0, 0, pl.ds(hh, 1), slice(None)) if parq else
                    (0, 0, hh, pl.ds(qi, 1), slice(None)))


def _fwd_kernel_streamed(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc,
                         acc_sc, *, causal, scale, hg, d, nk, tile,
                         bf16chain=False):
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

    def _attend(rows, cols, vis):
        for hh in range(hg):
            sl = slice(hh * d, (hh + 1) * d)
            new_m, new_l, new_acc = _online_step(
                q_ref[0, rows, sl], k_ref[0, cols, sl], v_ref[0, cols, sl],
                m_sc[hh, rows], l_sc[hh, rows], acc_sc[rows, sl], vis,
                scale, bf16chain)
            l_sc[hh, rows] = new_l
            acc_sc[rows, sl] = new_acc
            m_sc[hh, rows] = new_m

    _causal_cells(_attend, causal, qi, ki, block_q, block_k, tile)

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
                          sem, *, causal, scale, hg, d, block_k, nk, tile,
                          kv_parts=(0, 0), bf16chain=False):
    """Forward with EXPLICIT K/V streaming: K/V stay in HBM (ANY memory
    space) and block_k-sized chunks are double-buffered into VMEM scratch
    with async copies, so the fetch of chunk i+1 overlaps the softmax chain
    of chunk i.  Grid (B, n_hg, nq) like the resident kernel; O(block_k)
    K/V VMEM instead of O(S).  Under causal the scan stops after the
    diagonal block: the chunks before it run whole and unmasked in the
    fori loop, the block_q // block_k chunks of the band are unrolled
    behind it, each walked by its live sub-tiles."""
    block_q = q_ref.shape[1]
    hgd = hg * d
    bi = _pid(0)
    g = _pid(1)
    qi = _pid(2)
    # K/V are addressed by hand here: their parts' offsets (_kv_parts)
    # enter the column base where the other kernels' index maps take them
    k_base, v_base = (jax.lax.mul(g + _i32(off) if off else g, _i32(hgd))
                      for off in kv_parts)

    if causal:
        # only chunks up to the band end attend; rest are strictly future
        assert block_q % block_k == 0
        ratio = block_q // block_k
        num_full = jax.lax.mul(qi, _i32(ratio))
        kend = num_full + _i32(ratio)
    else:
        num_full = kend = _i32(nk)

    def kv_dma(slot, kb):
        start = jax.lax.mul(kb, _i32(block_k))
        ck = pltpu.make_async_copy(
            k_any.at[bi, pl.ds(start, block_k), pl.ds(k_base, hgd)],
            k_sc.at[slot], sem.at[slot, 0])
        cv = pltpu.make_async_copy(
            v_any.at[bi, pl.ds(start, block_k), pl.ds(v_base, hgd)],
            v_sc.at[slot], sem.at[slot, 1])
        return ck, cv

    ck0, cv0 = kv_dma(0, _i32(0))
    ck0.start()
    cv0.start()

    def chunk(kb):
        """Start the fetch of chunk kb+1, wait for chunk kb: its slot."""
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
        return slot

    def body(kb, carry):
        ms, ls, accs = carry     # per-head tuples: (BQ,), (BQ,), (BQ, D)
        slot = chunk(kb)
        new = [_online_step(q_ref[0, :, hh * d:(hh + 1) * d],
                            k_sc[slot, :, hh * d:(hh + 1) * d],
                            v_sc[slot, :, hh * d:(hh + 1) * d],
                            ms[hh], ls[hh], accs[hh], None, scale,
                            bf16chain) for hh in range(hg)]
        return tuple(zip(*new))

    init = (tuple(jnp.full((block_q,), jnp.float32(_NEG_INF), jnp.float32)
                  for _ in range(hg)),
            tuple(jnp.zeros((block_q,), jnp.float32) for _ in range(hg)),
            tuple(jnp.zeros((block_q, d), jnp.float32)
                  for _ in range(hg)))
    ms, ls, accs = jax.lax.fori_loop(_i32(0), num_full, body, init)
    sts = [_split_rows(ms[hh], ls[hh], accs[hh], tile if causal
                       else block_q) for hh in range(hg)]
    if causal:
        tri = _tri_vis(tile)
        for jj in range(ratio):
            slot = chunk(num_full + _i32(jj))
            runs = _band_runs(block_q, block_k, tile, -jj * block_k)
            for hh in range(hg):
                sl = slice(hh * d, (hh + 1) * d)
                _band_steps(
                    sts[hh], lambda rows: q_ref[0, rows, sl],
                    lambda cols: (k_sc[slot, cols, sl],
                                  v_sc[slot, cols, sl]),
                    runs, tri, scale, bf16chain)
    for hh in range(hg):
        _fwd_finish(sts[hh], o_ref, slice(hh * d, (hh + 1) * d), lse_ref,
                    (0, 0, hh, pl.ds(qi, 1), slice(None)))


def _flash_fwd(q3, k3, v3, causal, scale, d, interpret, spec, packed=False):
    """The forward's entry: settles what the program depends on beside
    its operands (which family, the band's tile), counts the call and its
    score elements, and hands over to the jitted builder, so that the
    layers of a model share ONE traced kernel instead of tracing one each.
    ``packed``: q3, k3 and v3 are the same ``(b, s, 3*h*d)`` buffer
    (:func:`_kv_parts`)."""
    from .flash_attention import note_fwd_call
    note_fwd_call("packed" if packed else "split")
    variant, block_q, block_k, hg = spec
    feats = variant_features(variant, _FWD_FEATURES)
    family = ("pipelined" if "pipelined" in feats else
              "resident" if _kv_fits_resident(k3.shape[1], hg * d)
              else "streamed")
    tile = _band_tile(block_k)
    if causal:
        # the registry lives outside the kernel modules (their bodies are
        # traced): the dispatch module writes the counter
        from .flash_attention import note_score_elements
        b, s, hd = q3.shape
        heads = hd // d // (3 if packed else 1)
        note_score_elements(*(b * heads * n
                              for n in score_elements(s, block_q, tile)))
    # trace with x64 off: the global x64 mode (needed for paddle's int64
    # semantics) surfaces i64/f64 intermediates that mosaic cannot lower
    with x64_scope(False):
        return _flash_fwd_inner(q3, k3, v3, causal, scale, d, interpret,
                                spec, family, tile, packed)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_fwd_inner(q3, k3, v3, causal, scale, d, interpret, spec, family,
                     tile, packed):
    variant, block_q, block_k, hg = spec
    feats = variant_features(variant, _FWD_FEATURES)
    bf16chain = "bf16chain" in feats
    b, s, hd = q3.shape
    if packed:
        hd //= 3
    sk = k3.shape[1]
    n_hg = hd // (hg * d)
    nq = s // block_q
    nk = sk // block_k
    hgd = hg * d
    q_spec3 = pl.BlockSpec((1, block_q, hgd), lambda bi, g, i: (bi, i, g))
    lse_shape = _sds((b, n_hg, hg, nq, block_q), jnp.float32, q3)
    out_shape = _sds((b, s, hd), q3.dtype, q3)
    if family == "pipelined":
        # explicit double-buffered K/V DMA — O(block_k) K/V VMEM at ANY
        # sequence length (an alternative to both the resident and the
        # grid-streamed paths; the autotuner decides when it wins)
        kernel = functools.partial(
            _fwd_kernel_pipelined, causal=causal, scale=scale, hg=hg, d=d,
            block_k=block_k, nk=nk, tile=tile,
            kv_parts=_kv_parts(packed, n_hg), bf16chain=bf16chain)
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
    if family == "resident":
        # fast path: whole K/V resident per cell, fori scan (measured
        # fastest at bench shapes)
        parq = "parq" in feats
        kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                                   hg=hg, d=d, block_k=block_k, tile=tile,
                                   bf16chain=bf16chain, parq=parq)
        kv_specs = _kv_specs((1, sk, hgd), lambda bi, g, i: (bi, 0, g),
                             packed, n_hg)
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
            in_specs=[q_spec3, *kv_specs],
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
                               scale=scale, hg=hg, d=d, nk=nk, tile=tile,
                               bf16chain=bf16chain)
    q_spec = pl.BlockSpec((1, block_q, hgd), lambda bi, g, i, j: (bi, i, g))
    kv_specs = _kv_specs((1, block_k, hgd),
                         lambda bi, g, i, j: (bi, j, g), packed, n_hg)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, n_hg, nq, nk),
        in_specs=[q_spec, *kv_specs],
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

def _row_stat(ref, hh, qi, rows):
    """Rows ``rows`` of head hh's lse/delta row of q block qi, (n,) f32:
    the whole (BQ,) row is loaded and the value sliced — Mosaic has no
    (1, t) load at a lane offset from a dynamically indexed row."""
    row = ref[0, 0, hh, pl.ds(qi, 1), :][0]
    return row if rows == slice(0, row.shape[0]) else row[rows]


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc, *,
                causal, scale, hg, d, nq, nk, tile, bf16chain=False):
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

    def _compute(rows, cols, vis):
        row0 = jax.lax.mul(qi, _i32(block_q))
        if rows.start:
            row0 = row0 + _i32(rows.start)
        n_rows = rows.stop - rows.start
        for hh in range(hg):
            sl = slice(hh * d, (hh + 1) * d)
            g = _bwd_head_math(
                q_ref[0, rows, sl], k_ref[0, cols, sl], v_ref[0, cols, sl],
                do_ref[0, rows, sl],
                _row_stat(lse_ref, hh, qi, rows),            # base-2
                _row_stat(delta_ref, hh, qi, rows),
                vis, scale, bf16chain)
            dv_sc[cols, sl] = dv_sc[cols, sl] + g["dv"]
            dk_sc[cols, sl] = dk_sc[cols, sl] + g["dk"]
            # dQ rows accumulate in the full-sequence scratch
            dq_sc[pl.ds(row0, n_rows), sl] = \
                dq_sc[pl.ds(row0, n_rows), sl] + g["dq"]

    # fully-visible cells run whole with no mask arithmetic, the band
    # cells by their live sub-tiles, strictly-future cells not at all (the
    # static grid still streams their prefetch: the price of pipelining)
    _causal_cells(_compute, causal, qi, ki, block_q, block_k, tile)

    @pl.when(qi == nq - 1)
    def _finalize_kv():
        dk_ref[0] = (jnp.float32(scale) * dk_sc[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(ki == nk - 1, qi == nq - 1))
    def _finalize_q():
        dq_ref[0] = (jnp.float32(scale) * dq_sc[...]).astype(dq_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_sc, *, causal, scale, hg, d, nk, tile,
                   bf16chain=False):
    """dQ-only backward for LONG sequences: grid (b, n_hg, nq, nk) with ki
    innermost, so dq accumulates in a BLOCK-sized scratch (no full-sequence
    scratch — the merged kernel's VMEM bound on the sequence length)."""
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]
    qi = _pid(2)
    ki = _pid(3)

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def _compute(rows, cols, vis):
        for hh in range(hg):
            sl = slice(hh * d, (hh + 1) * d)
            g = _bwd_head_math(
                q_ref[0, rows, sl], k_ref[0, cols, sl], v_ref[0, cols, sl],
                do_ref[0, rows, sl],
                _row_stat(lse_ref, hh, qi, rows),            # base-2
                _row_stat(delta_ref, hh, qi, rows),
                vis, scale, bf16chain, want_dkv=False)
            dq_sc[rows, sl] = dq_sc[rows, sl] + g["dq"]

    _causal_cells(_compute, causal, qi, ki, block_q, block_k, tile)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = (jnp.float32(scale) * dq_sc[...]).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_sc, dv_sc, *, causal, scale, hg, d,
                    nq, tile, bf16chain=False):
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

    def _compute(rows, cols, vis):
        for hh in range(hg):
            sl = slice(hh * d, (hh + 1) * d)
            g = _bwd_head_math(
                q_ref[0, rows, sl], k_ref[0, cols, sl], v_ref[0, cols, sl],
                do_ref[0, rows, sl],
                _row_stat(lse_ref, hh, qi, rows),            # base-2
                _row_stat(delta_ref, hh, qi, rows),
                vis, scale, bf16chain, want_dq=False)
            dv_sc[cols, sl] = dv_sc[cols, sl] + g["dv"]
            dk_sc[cols, sl] = dk_sc[cols, sl] + g["dk"]

    _causal_cells(_compute, causal, qi, ki, block_q, block_k, tile)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = (jnp.float32(scale) * dk_sc[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _cell_runs(causal, qi, ki, block_q, block_k, t):
    """:func:`_causal_cells` for STATIC block indices: the ``[(rows, cols,
    masked)]`` runs block pair (qi, ki) must attend — the whole pair, its
    live band runs, or nothing for a pair strictly in the future."""
    if causal:
        j = ki - qi * (block_q // block_k)
        if j >= 0:
            return _band_runs(block_q, block_k, t, -j * block_k) \
                if j < block_q // block_k else []
    return [(slice(0, block_q), slice(0, block_k), False)]


def _add_rows(parts, first, x, g):
    """Add the f32 block ``x``, whose first row is row ``first`` of the
    head, to the ``g``-row accumulators ``parts`` (by first row; an absent
    entry is zero: nothing is zeroed, nothing read back)."""
    for i in range(0, x.shape[0], g):
        piece = x if x.shape[0] == g else x[i:i + g]
        parts[first + i] = parts[first + i] + piece \
            if first + i in parts else piece


def _store_rows(ref, sl, parts, g, first, n, factor=None):
    """Write rows [first, first + n) of a head's output ONCE from their
    finished accumulators, scaled and cast in the same store."""
    assert sorted(parts) == list(range(first, first + n, g)), sorted(parts)
    for r0, x in parts.items():
        if factor is not None:
            x = jnp.float32(factor) * x
        ref[0, r0:r0 + g, sl] = x.astype(ref.dtype)


def _bwd_resident_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest,
                         causal, scale, hg, d, block_q, block_k, tile,
                         bf16chain=False):
    """The whole backward of one (batch, head group) in ONE grid cell.
    q/dO/O/dq: (1, S, HG*D), k/v/dk/dv: (1, SK, HG*D) whole-sequence
    blocks, each crossing HBM once; lse (and, from
    flash_attention_bshd_with_lse, the lse cotangent in its place beside
    it): (1, 1, HG, S) rows.  ``delta = rowsum(dO * O)`` is formed here, in
    f32 from the rows already held, so no caller builds it.  The walk over
    block pairs is Python-static (:func:`_cell_runs`): a pair strictly in
    the future is absent, every dq / dk / dv row range is the sum of a
    handful of block contributions held as values and stored once."""
    *dlse_ref, dq_ref, dk_ref, dv_ref = rest
    s, sk = q_ref.shape[1], k_ref.shape[1]
    # what the accumulators are cut by: the band's tile under causal
    # (block_q % block_k == 0 and t divides both), whole blocks otherwise
    gq, gk = (tile, tile) if causal else (block_q, block_k)
    tri = _tri_vis(tile) if causal else None
    for hh in range(hg):
        sl = slice(hh * d, (hh + 1) * d)
        stats = {}
        for q0 in range(0, s, block_q):
            rows = slice(q0, q0 + block_q)
            delta = jnp.sum(do_ref[0, rows, sl].astype(jnp.float32) *
                            o_ref[0, rows, sl].astype(jnp.float32), axis=-1)
            if dlse_ref:
                # dS = P * (dP - delta + dlse)
                delta = delta - dlse_ref[0][0, 0, hh, rows]
            stats[q0] = (lse_ref[0, 0, hh, rows], delta)        # base-2
        dq = {}
        for k0 in range(0, sk, block_k):
            dk, dv = {}, {}
            for q0 in range(0, s, block_q):
                lse, delta = stats[q0]
                for rows, cols, masked in _cell_runs(
                        causal, q0 // block_q, k0 // block_k, block_q,
                        block_k, tile):
                    whole = rows == slice(0, block_q)
                    ar = slice(q0 + rows.start, q0 + rows.stop)
                    ac = slice(k0 + cols.start, k0 + cols.stop)
                    g = _bwd_head_math(
                        q_ref[0, ar, sl], k_ref[0, ac, sl], v_ref[0, ac, sl],
                        do_ref[0, ar, sl], lse if whole else lse[rows],
                        delta if whole else delta[rows],
                        tri if masked else None, scale, bf16chain)
                    _add_rows(dv, ac.start, g["dv"], gk)
                    _add_rows(dk, ac.start, g["dk"], gk)
                    _add_rows(dq, ar.start, g["dq"], gq)
            _store_rows(dk_ref, sl, dk, gk, k0, block_k, scale)
            _store_rows(dv_ref, sl, dv, gk, k0, block_k)
        _store_rows(dq_ref, sl, dq, gq, 0, s, scale)


#: the backward builders are jitted like the forward's: one traced kernel
#: for all the layers of a model (operands dynamic, the rest static)
_BWD_JIT = functools.partial(jax.jit,
                             static_argnums=(6, 7, 8, 9, 10, 11, 12, 13))


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


@_BWD_JIT
def _bwd_dq(q3, k3, v3, do3, lse, delta, causal, scale, hg, d, spec,
            interpret, tile, packed):
    """The dq pallas_call of the split backward."""
    variant, block_q, block_k = spec
    feats = variant_features(variant, _BWD_FEATURES)
    b, s, hd = do3.shape
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
    kv_specs = _kv_specs((1, block_k, hgd),
                         lambda bi, g, i, j: (bi, j, g), packed, h // hg)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                          hg=hg, d=d, nk=nk,
                          tile=tile,
                          bf16chain="bf16chain" in feats),
        grid=(b, h // hg, nq, nk),
        in_specs=[q_spec_qout, *kv_specs, q_spec_qout, row_spec, row_spec],
        out_specs=q_spec_qout,
        out_shape=_sds((b, s, hd), q3.dtype, q3),
        scratch_shapes=[pltpu.VMEM((block_q, hgd), jnp.float32)],
        compiler_params=_SEQ2,
        name="flash_bwd_dq",
        interpret=interpret,
    )(q3, k3, v3, do3, lse5, delta5)


@_BWD_JIT
def _bwd_dkv(q3, k3, v3, do3, lse, delta, causal, scale, hg, d, spec,
             interpret, tile, packed):
    """The dk/dv pallas_call of the split backward."""
    variant, block_q, block_k = spec
    feats = variant_features(variant, _BWD_FEATURES)
    b, s, hd = do3.shape
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
    kv_map = lambda bi, g, i, j: (bi, i, g)
    kv_spec_kout = pl.BlockSpec((1, block_k, hgd), kv_map)
    kv_specs = _kv_specs((1, block_k, hgd), kv_map, packed, h // hg)
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                          hg=hg, d=d, nq=nq,
                          tile=tile,
                          bf16chain="bf16chain" in feats),
        grid=(b, h // hg, nk, nq),
        in_specs=[q_spec_kout, *kv_specs, q_spec_kout, row_spec, row_spec],
        out_specs=[kv_spec_kout, kv_spec_kout],
        out_shape=[_sds((b, sk, hd), k3.dtype, k3),
                   _sds((b, sk, hd), v3.dtype, v3)],
        scratch_shapes=[pltpu.VMEM((block_k, hgd), jnp.float32),
                        pltpu.VMEM((block_k, hgd), jnp.float32)],
        compiler_params=_SEQ2,
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q3, k3, v3, do3, lse5, delta5)


@_BWD_JIT
def _bwd_merged(q3, k3, v3, do3, lse, delta, causal, scale, hg, d, spec,
                interpret, tile, packed):
    """The merged dQ/dK/dV pallas_call."""
    variant, block_q, block_k = spec
    feats = variant_features(variant, _BWD_FEATURES)
    b, s, hd = do3.shape
    sk = k3.shape[1]
    h = hd // d
    nq = s // block_q
    nk = sk // block_k
    hgd = hg * d
    lse5 = _fold_lse(lse, b, h, hg, block_q)
    delta5 = _fold_rows(delta, b, h, hg, block_q)
    q_spec = pl.BlockSpec((1, block_q, hgd), lambda bi, g, i, j: (bi, j, g))
    kv_map = lambda bi, g, i, j: (bi, i, g)
    kv_spec = pl.BlockSpec((1, block_k, hgd), kv_map)
    kv_specs = _kv_specs((1, block_k, hgd), kv_map, packed, h // hg)
    row_spec = pl.BlockSpec((1, 1, hg, nq, block_q),
                            lambda bi, g, i, j: (bi, g, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, scale=scale,
                          hg=hg, d=d, nq=nq, nk=nk,
                          tile=tile,
                          bf16chain="bf16chain" in feats),
        grid=(b, h // hg, nk, nq),
        in_specs=[q_spec, *kv_specs, q_spec, row_spec, row_spec],
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


@_BWD_JIT
def _bwd_resident(q3, k3, v3, do3, o3, rows, causal, scale, hg, d, spec,
                  interpret, tile, packed):
    """The resident backward's pallas_call: grid (b, h // hg), both
    parallel.  ``rows``: (lse,) or (lse, dlse) — f32 row statistics in any
    fold of (b, h, s)."""
    variant, block_q, block_k = spec
    feats = variant_features(variant, _BWD_FEATURES)
    b, s, hd = do3.shape
    sk = k3.shape[1]
    n_hg = hd // (hg * d)
    hgd = hg * d
    cols = lambda bi, g: (bi, 0, g)
    q_spec = pl.BlockSpec((1, s, hgd), cols)
    kv_spec = pl.BlockSpec((1, sk, hgd), cols)
    kv_specs = _kv_specs((1, sk, hgd), cols, packed, n_hg)
    row_spec = pl.BlockSpec((1, 1, hg, s), lambda bi, g: (bi, g, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_resident_kernel, causal=causal, scale=scale,
                          hg=hg, d=d, block_q=block_q, block_k=block_k,
                          tile=tile, bf16chain="bf16chain" in feats),
        grid=(b, n_hg),
        in_specs=[q_spec, *kv_specs, q_spec, q_spec] +
        [row_spec] * len(rows),
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[
            _sds((b, s, hd), q3.dtype, q3),
            _sds((b, sk, hd), k3.dtype, k3),
            _sds((b, sk, hd), v3.dtype, v3),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_RESIDENT_BWD_VMEM_LIMIT),
        name="flash_bwd",
        interpret=interpret,
    )(q3, k3, v3, do3, o3, *(r.reshape(b, n_hg, hg, s) for r in rows))


def _bwd_entry(builder):
    """``call(q3, k3, v3, do3, lse, delta, causal, scale, hg, d, spec,
    interpret, packed=False)`` over a jitted builder (the resident one
    takes ``o3`` and its row statistics where the others take ``lse`` and
    ``delta``) — the production entry and the autotuner's runner entry;
    the band's tile is settled out here, where the builder's trace cache
    can see it."""
    def call(q3, k3, v3, do3, lse, delta, causal, scale, hg, d, spec,
             interpret, packed=False):
        return builder(q3, k3, v3, do3, lse, delta, causal, scale, hg, d,
                       spec, interpret, _band_tile(spec[2]), packed)
    return call


_bwd_dq_call = _bwd_entry(_bwd_dq)
_bwd_dkv_call = _bwd_entry(_bwd_dkv)
_bwd_merged_call = _bwd_entry(_bwd_merged)
_bwd_resident_call = _bwd_entry(_bwd_resident)


def _flash_bwd(q3, k3, v3, o3, lse, do3, causal, scale, d, interpret, spec,
               dlse=None, packed=False):
    # dlse: optional (b, h, s) f32 rows, the cotangent of a base-e lse
    # OUTPUT (flash_attention_bshd_with_lse): dS_ij = P_ij (dP_ij - delta_i
    # + dlse_i), so it enters as delta - dlse.
    # spec: ("resident" | "merged", variant, block_q, block_k, hg) or
    #       ("split", (variant, bq, bk), (variant, bq, bk), hg) — decided
    # by _resolve_specs from the shape (_bwd_plan).
    # packed: q3, k3 and v3 are the same (b, s, 3*h*d) buffer (_kv_parts);
    # dq, dk and dv leave as three (b, s, h*d) arrays either way.
    from .flash_attention import note_bwd_call
    note_bwd_call(spec[0])
    with x64_scope(False):
        b, s, hd = do3.shape
        h = hd // d
        if spec[0] == "resident":
            # the kernel takes O and forms delta itself; the lse
            # cotangent's rows ride beside the lse rows
            _, variant, block_q, block_k, hg = spec
            rows = (lse,) if dlse is None else \
                (lse, dlse.astype(jnp.float32))
            return _bwd_resident_call(q3, k3, v3, do3, o3, rows, causal,
                                      scale, hg, d,
                                      (variant, block_q, block_k), interpret,
                                      packed)
        # delta = rowsum(dO * O) per head in XLA, folded to the kernels'
        # (b, n_hg, hg, nq, bq) row layout per call
        delta = jnp.sum(
            do3.reshape(b, s, h, d).astype(jnp.float32) *
            o3.reshape(b, s, h, d).astype(jnp.float32), axis=-1)  # (b,s,h)
        if dlse is not None:
            delta = delta - jnp.moveaxis(dlse.astype(jnp.float32), 1, -1)
        if spec[0] == "split":
            _, dq_spec, dkv_spec, hg = spec
            dq = _bwd_dq_call(q3, k3, v3, do3, lse, delta, causal, scale,
                              hg, d, dq_spec, interpret, packed)
            dk, dv = _bwd_dkv_call(q3, k3, v3, do3, lse, delta, causal,
                                   scale, hg, d, dkv_spec, interpret, packed)
            return dq, dk, dv
        _, variant, block_q, block_k, hg = spec
        return _bwd_merged_call(q3, k3, v3, do3, lse, delta, causal, scale,
                                hg, d, (variant, block_q, block_k),
                                interpret, packed)


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _flash_packed(qkv3, causal, scale, d, interpret, fwd_spec, bwd_spec):
    """:func:`_flash` of the three column parts of ``qkv3`` (b, s, 3*h*d),
    read where they lie (:func:`_kv_parts`): a Mosaic call cannot take a
    slice as an operand, so slicing first costs a pass over the buffer and
    three written copies a call."""
    out, _ = _flash_fwd(qkv3, qkv3, qkv3, causal, scale, d, interpret,
                        fwd_spec, packed=True)
    return out


def _flash_packed_vjp_fwd(qkv3, causal, scale, d, interpret, fwd_spec,
                          bwd_spec):
    out, lse = _flash_fwd(qkv3, qkv3, qkv3, causal, scale, d, interpret,
                          fwd_spec, packed=True)
    return out, (qkv3, out, lse)


def _flash_packed_vjp_bwd(causal, scale, d, interpret, fwd_spec, bwd_spec,
                          res, g):
    qkv3, out, lse = res
    dq, dk, dv = _flash_bwd(qkv3, qkv3, qkv3, out, lse, g, causal, scale, d,
                            interpret, bwd_spec, packed=True)
    # the cotangent as a SUM OF PADS, the transpose of three slices: XLA
    # takes dq, dk and dv as operands of the projection's weight- and
    # input-gradient GEMM fusions and of the bias reduce, and no (b, s,
    # 3*h*d) buffer exists.  A concatenate here becomes three
    # dynamic-update-slice passes over one (compiled for a described v5e;
    # tests/test_flash_tpu_compile.py holds the program to it)
    hd = dq.shape[2]
    zero = jnp.zeros((), dq.dtype)
    return (sum(jax.lax.pad(x, zero, ((0, 0, 0), (0, 0, 0),
                                      (i * hd, (2 - i) * hd, 0)))
                for i, x in enumerate((dq, dk, dv))),)


_flash_packed.defvjp(_flash_packed_vjp_fwd, _flash_packed_vjp_bwd)


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
    # the (b, n_hg, hg, nq, bq) base-2 lse cotangent as (b, h, s) rows,
    # base-e: lse2 = lse_e * log2e, so dlse_e = dlse2 * log2e
    dlse = dlse2.reshape(b, h, s) * jnp.float32(_LOG2E)
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


def _bwd_group_fits(path, s, sk, hg, d, causal, bq, bk) -> bool:
    """Whether the ``flash_bwd`` family's kernel on ``path`` (resident, or
    the merged one) can hold this head group at these blocks."""
    if path == "resident":
        return _resident_bwd_fits(s, sk, hg * d, causal, bq, bk)
    return max(s, sk) * hg * d * 4 <= _DQ_SCRATCH_BUDGET


def _sane_bwd_grouped(path, cand, s, sk, h, d, causal, default):
    """A resolved/pinned ``flash_bwd`` candidate as the ``path`` (resident
    or merged) the shape chose: off-spec blocks, variant or head group, or
    a working set the path cannot hold, fall back to the default."""
    hg = cand.get("config", {}).get("hg")
    variant, bq, bk = _sane_bwd_blocks(cand, s, sk, causal, default[:2])
    if not (_valid_hg(hg, h, d) and
            _bwd_group_fits(path, s, sk, hg, d, causal, bq, bk)):
        return (path, "base") + default
    return (path, variant, bq, bk, hg)


def _resolve_specs(b, s, sk, h, d, dtype, causal, block_q, block_k, hg_f,
                   hg_b, variant=None, tie_groups=False,
                   use_autotune=True):
    """(fwd_spec, bwd_spec) for one call: an explicit ``variant`` or
    caller-pinned block sizes (``use_autotune=False``) bypass the autotuner
    entirely (the A/B and parity-test entry); otherwise the specs resolve
    through autotune.resolve() with the hand-tuned values as the registered
    defaults — identical programs until tuning runs.  The backward's path
    (:func:`_bwd_plan`) is the shape's in both cases."""
    path, hg_b = _bwd_plan(s, sk, h, d, causal, block_q, block_k, hg_b)
    if variant is not None or not use_autotune:
        variant = variant or "base"
        fv = canon_variant(variant_features(variant, _FWD_FEATURES))
        bv = bwd_variant_of(variant)
        fwd_spec = (fv, block_q, block_k, hg_f)
        bwd_spec = (("split", (bv, block_q, block_k),
                     (bv, block_q, block_k), hg_b) if path == "split"
                    else (path, bv, block_q, block_k, hg_b))
        return fwd_spec, bwd_spec
    from . import autotune as at
    key = autotune_key(b, s, sk, h, d, dtype, causal)
    fwd_spec = _sane_fwd_spec(at.resolve("flash_fwd", key), s, sk, h, d,
                              causal, (block_q, block_k, hg_f))
    if path == "split":
        bwd_spec = ("split",
                    _sane_bwd_blocks(at.resolve("flash_bwd_dq", key),
                                     s, sk, causal, (block_q, block_k)),
                    _sane_bwd_blocks(at.resolve("flash_bwd_dkv", key),
                                     s, sk, causal, (block_q, block_k)),
                    hg_b)
    else:
        bwd_spec = _sane_bwd_grouped(path, at.resolve("flash_bwd", key),
                                     s, sk, h, d, causal,
                                     (block_q, block_k, hg_b))
    if tie_groups and path != "resident":
        # one group for both directions: the lse OUTPUT layout must match
        # what the caller-visible (b, s, h) unfold assumes alongside the
        # backward's consumption (flash_attention_bshd_with_lse).  A tuned
        # fwd winner with a DIFFERENT head group is discarded for the
        # hand-tuned default rather than silently re-grouped — the
        # (variant, blocks, hg) combination after a re-group was never
        # timed, and alternate-hg candidates differ ONLY by hg.  (The
        # resident backward reads the rows in any fold of (b, h, s).)
        hg = bwd_spec[3] if path == "split" else bwd_spec[4]
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


def _key_bwd_plan(key):
    """``(path, bq0, bk0, hg)``: what production runs for ``key`` by
    default — the ``flash_bwd`` family's first candidate."""
    s, sk, h, d, causal = (key[k] for k in ("s", "sk", "h", "d", "causal"))
    bq0, bk0, _, hg_b = _default_cfg(key)
    path, hg = _bwd_plan(s, sk, h, d, causal, bq0, bk0, hg_b)
    return path, bq0, bk0, hg


def _bwd_candidates_merged(key):
    """The ``flash_bwd`` family's candidates: for the resident backward
    where the shape takes it, the merged one elsewhere, each within what
    that path can hold."""
    s, sk, h, d, causal = (key[k] for k in ("s", "sk", "h", "d", "causal"))
    path, bq0, bk0, hg_b = _key_bwd_plan(key)
    cands = [{"variant": "base",
              "config": {"block_q": bq0, "block_k": bk0, "hg": hg_b}}]
    for bq, bk in _candidate_blocks(s, sk, causal, bq0, bk0):
        if (bq, bk) != (bq0, bk0) and not _bwd_group_fits(
                path, s, sk, hg_b, d, causal, bq, bk):
            continue
        for v in (["base"] if (bq, bk) != (bq0, bk0) else []) + \
                list(_CAND_BWD_VARIANTS):
            cand = {"variant": v,
                    "config": {"block_q": bq, "block_k": bk, "hg": hg_b}}
            if cand not in cands:
                cands.append(cand)
    for hg in _aligned_groups(h, d):
        if hg != hg_b and hg * d <= 256 and \
                _bwd_group_fits(path, s, sk, hg, d, causal, bq0, bk0):
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
        call, rows = _bwd_family_call(which, key)

        def timed(q, k, v, do, *rest):
            # same x64-off trace scope as the production entry
            # (_flash_bwd) — under the global x64 mode the candidate
            # would otherwise lower a different (or unlowerable) program
            # than the one production runs
            with x64_scope(False):
                return call(q, k, v, do, *rest, causal, data["scale"], hg,
                            d, spec, data["interpret"])
        fn = jax.jit(timed)

        def run():
            jax.block_until_ready(fn(
                data["q3"], data["k3"], data["v3"], data["do3"],
                *rows(data["out"], data["lse"], data["delta"])))
        return run
    return make


def _bwd_family_call(which, key):
    """``(call, rows)`` of a backward family at ``key``: the entry the
    family's candidates run through, and ``rows(out, lse, delta)`` — the
    two operands it takes after dO (the resident backward, which the
    ``flash_bwd`` family is wherever the shape takes it, wants O and the
    lse rows; the others lse and delta)."""
    if which == "merged" and _key_bwd_plan(key)[0] == "resident":
        return _bwd_resident_call, lambda out, lse, delta: (out, (lse,))
    return ({"merged": _bwd_merged_call, "dq": _bwd_dq_call,
             "dkv": _bwd_dkv_call}[which],
            lambda out, lse, delta: (lse, delta))


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
        call, rows = _bwd_family_call(which, key)

        def fn(q, k, v, do, *rest):
            with x64_scope(False):
                return call(q, k, v, do, *rest, causal, scale, hg, d, spec,
                            interpret)
        sds = jax.ShapeDtypeStruct
        # lse/delta in the layout the default forward produces (what the
        # production bwd — and the timed runner — actually receives)
        return fn, (sds((b, s, h * d), dtype), sds((b, sk, h * d), dtype),
                    sds((b, sk, h * d), dtype), sds((b, s, h * d), dtype),
                    *rows(sds((b, s, h * d), dtype),
                          sds((b, h // hg_b, hg_b, s // bq0, bq0),
                              jnp.float32),
                          sds((b, s, h), jnp.float32)))
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


def _native_specs(b, s, sk, h, d, dtype, causal, block_q, block_k, variant):
    """``(fwd_spec, bwd_spec)`` of a :func:`flash_attention_bshd_native`
    or :func:`flash_attention_packed_native` call: the two resolve alike,
    so the same shape runs the same kernels whichever way its operands
    arrive."""
    hg_b = _pick_head_group(h, d, max(s, sk))
    hg_f = _pick_fwd_head_group(h, d, max(s, sk), hg_b)
    default_blocks = (block_q, block_k) == (DEFAULT_BLOCK_Q,
                                            DEFAULT_BLOCK_K)
    block_q, block_k = _prep_blocks(s, sk, causal, block_q, block_k,
                                    "flash_attention")
    return _resolve_specs(
        b, s, sk, h, d, dtype, causal, block_q, block_k, hg_f, hg_b,
        variant=variant, use_autotune=default_blocks)


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
    fwd_spec, bwd_spec = _native_specs(b, s, sk, h, d, q.dtype, causal,
                                       block_q, block_k, variant)
    q3 = q.reshape(b, s, h * d)
    k3 = k.reshape(b, sk, h * d)
    v3 = v.reshape(b, sk, h * d)
    out = _flash(q3, k3, v3, causal, float(scale), d, interpret, fwd_spec,
                 bwd_spec)
    return out.reshape(b, s, h, d)


def flash_attention_packed_native(qkv, num_heads, causal=False, scale=None,
                                  block_q=DEFAULT_BLOCK_Q,
                                  block_k=DEFAULT_BLOCK_K, interpret=False,
                                  variant=None):
    """:func:`flash_attention_bshd_native` of a fused projection's output:
    qkv (B, S, 3*H*D) in ``[q | k | v]`` column order -> (B, S, H, D).  The
    kernels read q, k and v where the GEMM wrote them, through their block
    index maps; the result and the gradient equal those of the three
    slices bit for bit (same kernels, same specs, same bytes)."""
    b, s, width = qkv.shape
    h = num_heads
    if width % (3 * h):
        raise ValueError("flash_attention_packed: a last dimension of %d "
                         "is not 3 x %d heads x a head size" % (width, h))
    d = width // (3 * h)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    fwd_spec, bwd_spec = _native_specs(b, s, s, h, d, qkv.dtype, causal,
                                       block_q, block_k, variant)
    out = _flash_packed(qkv, causal, float(scale), d, interpret, fwd_spec,
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
