"""Static-shape KV cache layouts — the serving engine's memory.

The decode path's non-negotiable TPU precondition is a *static-shape*
program: the legacy cache grew by ``concat`` each token, so its shape
changed every step and **every generated token retraced and recompiled
the whole model**.  Two static layouts live here:

* :class:`SlottedKVCache` — per-slot contiguous (PR 5):

      k, v : (num_slots, layers, max_len, heads, head_dim)
      lengths : (num_slots,) int32           # valid prefix per slot

  Every slot pays (and the decode read streams around) a full
  ``max_len`` buffer no matter how many tokens it actually holds.

* :class:`PagedKVCache` — vLLM-style block-structured memory
  (PagedAttention, SOSP '23) adapted to XLA's static-shape discipline:

      k, v       : (num_pages, layers, page_size, heads, head_dim)
      page_table : (num_slots, max_pages) int32   # page ids per slot
      lengths    : (num_slots,) int32

  A slot's tokens live in the fixed pool pages its page-table row maps;
  decode appends scatter into the slot's current *tail* page and
  attention gathers only mapped pages.  Memory (and the KV read bound a
  page-aware schedule pays) scales with *actual* lengths, and identical
  prompt prefixes can map the SAME refcounted pages (hash-based prefix
  sharing — ``serving/pages.py`` owns the host-side allocator:
  free list, refcounts, prefix hashes, copy-on-write decisions).  All
  of it stays compile-once: the page table, lengths, and gather indices
  are ordinary traced int32 arrays.

**int8 quantized KV (ISSUE 8).**  Either layout can store the pool as
int8 codes plus per-(row, head) f32 scales (``kv_dtype="int8"`` at
:meth:`create`): appends *quantize in-program* (symmetric amax/127 grid
— :func:`quantize_kv`) and the decode-attention q8 variants dequantize
inline in the gather, so decode HBM traffic per K/V row drops from
``head_dim * 2`` bytes (bf16) to ``head_dim + 4`` (int8 codes + one f32
scale per head).  The scale pools mirror the code pools' page/slot
structure:

      k_scale, v_scale : (num_pages, layers, page_size, heads)  f32   (paged)
      k_scale, v_scale : (num_slots, layers, max_len, heads)    f32   (slotted)

**fp8 quantized KV (``kv_dtype="fp8"`` — ISSUE 20).**  The same
plumbing runs float8_e4m3fn codes: scale layout, scatter paths and the
dequant-in-gather kernels are shared, and :func:`quantize_kv` swaps only
the grid — amax/448 scaling with a clip to ±448 BEFORE the cast (e4m3
has no inf; an overflowing cast encodes NaN, so saturation must happen
in f32).  The e4m3 row prices exactly like the int8 row (1-byte codes +
one f32 scale per head); the trade is int8's round-to-nearest ~1/254
grid for a 3-mantissa-bit (~1/16 relative step) dtype the MXU can
multiply natively on current TPUs.

Attention over either layout is masked to each slot's valid prefix: the
query token at block offset ``j`` of a slot with pre-append length ``n``
sits at global position ``n + j`` and may attend keys ``t <= n + j``.
That one formula covers batched decode (``j = 0``), multi-token appends
(speculative verify scores ``k + 1`` positions through exactly this
path), chunked prefill (``j`` ranges over the chunk), and whole-prompt
prefill (``n = 0`` reduces it to the causal mask).

*Views* adapt a cache to the model's per-layer walk (they are
trace-time carriers, not pytrees — the arrays they hold thread through
``jit`` as ordinary tracers):

* :class:`DecodeView` / :class:`PagedDecodeView` — batched: batch dim ==
  num_slots, every active slot advances together in one fixed-shape
  program.
* :class:`PrefillView` — slotted bucketed prefill: one sequence, one
  (dynamic) slot index, writes rows ``[0, bucket)`` and runs plain
  block-causal attention (nothing prior to attend to).
* :class:`PagedPrefillChunkView` — one fixed-size chunk of one slot's
  prompt: writes positions ``[n, n + valid)`` into mapped pages and
  attends to the full mapped past + itself (the chunked-prefill
  program the engine interleaves with decode).

A view's *carry fields* — the traced arrays it threads through the
model's per-layer walk — are dynamic: ``k, v`` always, ``k_scale, v_scale``
when the cache is quantized, the page table for paged views,
``lengths``, and (opt-in) a ``quant_err`` f32 scalar accumulating the
max abs dequantization error of the step's appends (the
``serving.kv_quant_error`` gauge).  :meth:`_CacheView.carry_fields`
is the single source of that ordering.

Dependency note: this module is imported by ``models/gpt.py`` and must
stay model-free (jax + the decode-attention kernel family only).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# the int8 grid lives with the q8 kernels (ONE canonical definition —
# the autotune runners synthesize operands through the same math, so the
# cache's writes and the kernels' reads can never drift); re-exported
# here as serving API
from ..kernels.decode_attention import dequantize_kv, quantize_kv
from ..observability import scopes as _scopes

__all__ = ["SlottedKVCache", "DecodeView", "PrefillView", "PagedKVCache",
           "PagedDecodeView", "PagedPrefillChunkView", "is_cache_view",
           "quantize_kv", "dequantize_kv", "np_native_view",
           "np_restore_view"]


def np_native_view(a):
    """``(host array, original dtype)`` with the array viewed in an
    npz-serializable dtype.  npz cannot round-trip ml_dtypes — a
    bfloat16 pool saves as void ``|V2`` and reloads unusable — so
    non-numpy-native pool dtypes serialize as a byte-exact unsigned
    view; :func:`np_restore_view` undoes it.  The KV spill transports
    (``serving/kv_tier.py``, ``serving/disagg.py``) share this pair so
    their staging files can never drift in dtype handling."""
    a = np.asarray(a)
    dt = a.dtype
    if dt.kind not in "fiu":
        a = a.view("u%d" % dt.itemsize)
    return a, dt


def np_restore_view(a, dtype):
    """Undo :func:`np_native_view`: reinterpret the loaded bytes in the
    original (possibly non-native) dtype."""
    return a.view(dtype) if a.dtype != dtype else a


def _as_kv_dtypes(kv_dtype):
    """(code dtype, scale dtype or None) for a cache ``kv_dtype``.
    Accepts the spelled dtypes plus the ``"fp8"`` shorthand for
    float8_e4m3fn (ISSUE 20: the e4m3 pool shares the int8 layout —
    same scale pools, same 1-byte codes, different grid constant)."""
    if kv_dtype is None:
        return None, None
    if isinstance(kv_dtype, str) and kv_dtype.strip().lower() == "fp8":
        kv_dtype = jnp.float8_e4m3fn
    dt = jnp.dtype(kv_dtype)
    if dt not in (jnp.dtype(jnp.int8), jnp.dtype(jnp.float8_e4m3fn)):
        raise ValueError("kv_dtype %r unsupported (int8 or fp8/"
                         "float8_e4m3fn)" % (kv_dtype,))
    return dt, jnp.float32


def _append_quant_err(prev, pairs):
    """Fold the max abs dequant error of freshly quantized appends into
    the running ``quant_err`` scalar (``prev`` None = tracking off)."""
    if prev is None:
        return None
    err = prev
    for x, q, s in pairs:
        d = dequantize_kv(q, s, jnp.float32) - x.astype(jnp.float32)
        err = jnp.maximum(err, jnp.max(jnp.abs(d)))
    return err


@jax.tree_util.register_pytree_node_class
class SlottedKVCache:
    """The preallocated cache state.  A registered pytree, so it passes
    through ``jax.jit`` boundaries (and ``donate_argnums``) directly.
    ``k_scale``/``v_scale`` are the per-(row, head) f32 scale pools of
    the int8 layout (None for the unquantized one)."""

    def __init__(self, k, v, lengths, k_scale=None, v_scale=None):
        self.k = k
        self.v = v
        self.lengths = lengths
        self.k_scale = k_scale
        self.v_scale = v_scale

    def tree_flatten(self):
        return (self.k, self.v, self.lengths, self.k_scale,
                self.v_scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @classmethod
    def create(cls, num_slots, num_layers, max_len, num_heads, head_dim,
               dtype="float32", kv_dtype=None):
        code_dt, scale_dt = _as_kv_dtypes(kv_dtype)
        pool_dt = dtype if code_dt is None else code_dt
        shape = (int(num_slots), int(num_layers), int(max_len),
                 int(num_heads), int(head_dim))
        ks = vs = None
        if scale_dt is not None:
            ks = jnp.zeros(shape[:-1], scale_dt)
            vs = jnp.zeros(shape[:-1], scale_dt)
        return cls(jnp.zeros(shape, pool_dt), jnp.zeros(shape, pool_dt),
                   jnp.zeros((int(num_slots),), jnp.int32),
                   k_scale=ks, v_scale=vs)

    @property
    def quantized(self):
        return self.k_scale is not None

    # -- static geometry (python ints — safe at trace time) ----------------
    @property
    def num_slots(self):
        return int(self.k.shape[0])

    @property
    def num_layers(self):
        return int(self.k.shape[1])

    @property
    def max_len(self):
        return int(self.k.shape[2])

    def __repr__(self):
        return ("SlottedKVCache(slots=%d, layers=%d, max_len=%d, heads=%d, "
                "head_dim=%d, dtype=%s)"
                % (self.k.shape + (self.k.dtype,)))


@jax.tree_util.register_pytree_node_class
class PagedKVCache:
    """Block-structured cache state: a fixed pool of fixed-size KV pages
    plus a per-slot page table.  A registered pytree, so it passes through
    ``jax.jit`` boundaries (and ``donate_argnums``) directly.  Unmapped
    page-table entries hold 0 — they gather page 0's bytes, which the
    length mask discards before they reach the softmax.  ``k_scale``/
    ``v_scale`` are the per-(page row, head) f32 scale pools of the int8
    layout (None for the unquantized one)."""

    def __init__(self, k, v, page_table, lengths, declared_max_len=None,
                 k_scale=None, v_scale=None):
        self.k = k
        self.v = v
        self.page_table = page_table
        self.lengths = lengths
        self.k_scale = k_scale
        self.v_scale = v_scale
        # the DECLARED length budget, when tighter than pool capacity
        # (max_len % page_size != 0 leaves dead rows in the tail page);
        # static aux data, so it survives jit boundaries and tree maps
        self.declared_max_len = (None if declared_max_len is None
                                 else int(declared_max_len))

    def tree_flatten(self):
        return ((self.k, self.v, self.page_table, self.lengths,
                 self.k_scale, self.v_scale), self.declared_max_len)

    @classmethod
    def tree_unflatten(cls, aux, children):
        k, v, table, lengths, ks, vs = children
        return cls(k, v, table, lengths, declared_max_len=aux,
                   k_scale=ks, v_scale=vs)

    @classmethod
    def create(cls, num_pages, num_layers, page_size, num_heads, head_dim,
               num_slots, max_pages, dtype="float32", kv_dtype=None):
        code_dt, scale_dt = _as_kv_dtypes(kv_dtype)
        pool_dt = dtype if code_dt is None else code_dt
        shape = (int(num_pages), int(num_layers), int(page_size),
                 int(num_heads), int(head_dim))
        ks = vs = None
        if scale_dt is not None:
            ks = jnp.zeros(shape[:-1], scale_dt)
            vs = jnp.zeros(shape[:-1], scale_dt)
        return cls(jnp.zeros(shape, pool_dt), jnp.zeros(shape, pool_dt),
                   jnp.zeros((int(num_slots), int(max_pages)), jnp.int32),
                   jnp.zeros((int(num_slots),), jnp.int32),
                   k_scale=ks, v_scale=vs)

    @classmethod
    def create_dense(cls, num_slots, num_layers, max_len, num_heads,
                     head_dim, page_size, dtype="float32", kv_dtype=None):
        """A pool with exactly one page set per slot, identity-mapped
        (slot ``i`` owns pages ``[i*max_pages, (i+1)*max_pages)``) — the
        allocator-free layout for model-level use (``gen_paged_cache``):
        capacity matches the slotted cache, only the memory is paged."""
        max_pages = -(-int(max_len) // int(page_size))
        cache = cls.create(int(num_slots) * max_pages, num_layers,
                           page_size, num_heads, head_dim, num_slots,
                           max_pages, dtype, kv_dtype=kv_dtype)
        table = jnp.arange(int(num_slots) * max_pages,
                           dtype=jnp.int32).reshape(int(num_slots),
                                                    max_pages)
        return cls(cache.k, cache.v, table, cache.lengths,
                   declared_max_len=int(max_len),
                   k_scale=cache.k_scale, v_scale=cache.v_scale)

    @property
    def quantized(self):
        return self.k_scale is not None

    # -- static geometry (python ints — safe at trace time) ----------------
    @property
    def num_pages(self):
        return int(self.k.shape[0])

    @property
    def num_layers(self):
        return int(self.k.shape[1])

    @property
    def page_size(self):
        return int(self.k.shape[2])

    @property
    def num_slots(self):
        return int(self.page_table.shape[0])

    @property
    def max_pages(self):
        return int(self.page_table.shape[1])

    @property
    def max_len(self):
        cap = self.max_pages * self.page_size
        return cap if self.declared_max_len is None \
            else min(self.declared_max_len, cap)

    def __repr__(self):
        return ("PagedKVCache(pages=%d, layers=%d, page_size=%d, heads=%d, "
                "head_dim=%d, slots=%d, max_pages=%d, dtype=%s)"
                % (self.k.shape + self.page_table.shape[:2]
                   + (self.k.dtype,)))


def is_cache_view(obj) -> bool:
    return isinstance(obj, _CacheView)


def _unwrap(x):
    return x._array if hasattr(x, "_array") else x


def paged_scatter(kc, vc, layer, table, pos, valid, k_new, v_new,
                  ksc=None, vsc=None, ks_new=None, vs_new=None):
    """Scatter ``k_new/v_new: (B, s, heads, head_dim)`` into page rows.

    ``table: (B, max_pages)`` maps each lane's pages; ``pos: (B, s)`` are
    global token positions; entries with ``valid`` False (inactive decode
    lanes, chunk padding) — or positions past the table — are routed to
    page id ``num_pages``, an out-of-bounds index XLA's default scatter
    mode DROPS (the same trick the slotted cache uses for rows past
    ``max_len``).  Distinct valid lanes never collide: the allocator
    copy-on-writes any shared page before a write can target it.  For the
    int8 layout, ``ks_new/vs_new: (B, s, heads)`` scale rows scatter into
    the ``ksc/vsc`` scale pools through the SAME routed indices.
    Returns ``(kc, vc, ksc, vsc)`` (scale pools pass through as None
    when unquantized)."""
    with _scopes.scope(_scopes.KV_WRITE):
        return _paged_scatter(kc, vc, layer, table, pos, valid, k_new,
                              v_new, ksc, vsc, ks_new, vs_new)


def _paged_scatter(kc, vc, layer, table, pos, valid, k_new, v_new, ksc, vsc,
                   ks_new, vs_new):
    P = int(kc.shape[2])
    max_pages = int(table.shape[1])
    num_pages = int(kc.shape[0])
    page_idx = pos // P                                    # (B, s) int32
    safe_idx = jnp.clip(page_idx, 0, max_pages - 1)
    page_id = jnp.take_along_axis(table, safe_idx, axis=1,
                                  mode="promise_in_bounds")
    page_id = jnp.where(valid & (page_idx < max_pages), page_id,
                        jnp.asarray(num_pages, jnp.int32))
    row = pos % P
    l_idx = jnp.asarray(layer, jnp.int32)
    kc = kc.at[page_id, l_idx, row].set(k_new.astype(kc.dtype))
    vc = vc.at[page_id, l_idx, row].set(v_new.astype(vc.dtype))
    if ksc is not None:
        ksc = ksc.at[page_id, l_idx, row].set(ks_new.astype(ksc.dtype))
        vsc = vsc.at[page_id, l_idx, row].set(vs_new.astype(vsc.dtype))
    return kc, vc, ksc, vsc


class _CacheView:
    """Trace-time carrier threading the cache arrays through the model's
    per-layer walk.  Layers call :meth:`attend` (Tensor-level, tape-aware)
    in order; the view allocates layer indices from an internal cursor.

    :meth:`carry_fields` names the traced arrays the view threads through
    the walk (each :meth:`attend` passes them across its ``call``
    boundary); :meth:`mutated_fields` is the subset a layer MUTATES —
    ``k, v``, plus the scale pools when the cache is quantized, plus the
    ``quant_err`` accumulator when tracking is on."""

    #: layout-specific carry fields between the scale pools and lengths
    #: (the paged views add "page_table")
    _extra_fields = ()
    #: the role of the view's attention in a trace (observability.scopes);
    #: the append is ``kv_write`` in every view
    _attn_scope = _scopes.DECODE_ATTN

    def __init__(self, cache, track_quant_err=False):
        self.k = _unwrap(cache.k)
        self.v = _unwrap(cache.v)
        ks = getattr(cache, "k_scale", None)
        vs = getattr(cache, "v_scale", None)
        self.k_scale = None if ks is None else _unwrap(ks)
        self.v_scale = None if vs is None else _unwrap(vs)
        self.lengths = _unwrap(cache.lengths)
        # opt-in per-step quantization-error accumulator (a traced f32
        # scalar carried through the walk; the serving.kv_quant_error
        # gauge reads it from the entry's outputs)
        self.quant_err = (jnp.zeros((), jnp.float32)
                          if (track_quant_err and self.quantized) else None)
        self._layer = 0

    @property
    def quantized(self):
        return self.k_scale is not None

    def carry_fields(self):
        f = ["k", "v"]
        if self.quantized:
            f += ["k_scale", "v_scale"]
        f += list(self._extra_fields)
        f.append("lengths")
        if self.quant_err is not None:
            f.append("quant_err")
        return tuple(f)

    def mutated_fields(self):
        f = ["k", "v"]
        if self.quantized:
            f += ["k_scale", "v_scale"]
        if self.quant_err is not None:
            f.append("quant_err")
        return tuple(f)

    def _alloc_layer(self) -> int:
        i = self._layer
        if i >= int(self.k.shape[1]):
            raise ValueError(
                "cache view exhausted: model has more attention layers "
                "than the cache's layer axis (%d)" % (self.k.shape[1],))
        self._layer = i + 1
        return i

    def carry_arrays(self):
        """The traced arrays :meth:`attend` passes across its ``call``
        boundary, in :meth:`carry_fields` order."""
        return tuple(getattr(self, f) for f in self.carry_fields())

    def attend(self, q, k_new, v_new, scale=None):
        """Tensor-level append+attend (dispatches through core.dispatch.call
        so eager autograd bookkeeping stays consistent)."""
        from ..core.dispatch import call
        layer = self._alloc_layer()
        carry = self.carry_arrays()
        n = len(carry)

        def raw(*args):
            return self._append_attend_raw(
                layer, args[:n], args[n], args[n + 1], args[n + 2], scale)

        res = call(raw, *carry, q, k_new, v_new,
                   name="slotted_kv_attend")
        for f, a in zip(self.mutated_fields(), res[1:]):
            setattr(self, f, _unwrap(a))
        return res[0]

    # -- shared quantized-append helper ------------------------------------

    def _quantize_new(self, c, k_new, v_new):
        """Quantize fresh K/V rows and fold their dequant error into the
        carried accumulator; returns (kq, ks, vq, vs, new_err)."""
        # the pool's dtype IS the grid selector (int8 or e4m3)
        with _scopes.scope(_scopes.KV_WRITE):
            kq, ks = quantize_kv(k_new, c["k"].dtype)
            vq, vs = quantize_kv(v_new, c["v"].dtype)
            err = _append_quant_err(c.get("quant_err"),
                                    ((k_new, kq, ks), (v_new, vq, vs)))
        return kq, ks, vq, vs, err


class DecodeView(_CacheView):
    """Batched decode: q/k/v arrive as (num_slots, s, heads, head_dim);
    each slot's ``s`` new tokens are written at rows
    ``[lengths[b], lengths[b] + s)`` and attention is masked to
    ``t <= lengths[b] + j``.  ``active`` gates which slots advance their
    length counter at :meth:`finalize` (inactive slots still compute —
    the program shape never changes — but their writes land past their
    frozen valid prefix and are overwritten on slot reuse)."""

    def __init__(self, cache: SlottedKVCache, active=None,
                 track_quant_err=False):
        super().__init__(cache, track_quant_err=track_quant_err)
        self.active = None if active is None else _unwrap(active)
        self._steps = 0

    def position_ids(self, batch, seq_len):
        if batch != int(self.k.shape[0]):
            raise ValueError(
                "batched decode needs batch == num_slots (%d), got %d — "
                "use PrefillView for single sequences"
                % (self.k.shape[0], batch))
        return (self.lengths[:, None]
                + jnp.arange(seq_len, dtype=jnp.int32)[None, :])

    def _append_attend_raw(self, layer, carry, q, k_new, v_new, scale):
        from ..kernels.decode_attention import decode_attention
        c = dict(zip(self.carry_fields(), carry))
        kc, vc, lengths = c["k"], c["v"], c["lengths"]
        s = int(q.shape[1])
        self._steps = s
        b_idx = jnp.arange(kc.shape[0], dtype=jnp.int32)[:, None]
        t_idx = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        # one scatter into the (donated) full cache buffer per array; XLA
        # updates in place (the operand chains through each layer's write).
        # Rows past max_len (a slot the scheduler failed to evict) drop.
        if self.quantized:
            kq, ks, vq, vs, err = self._quantize_new(c, k_new, v_new)
            with _scopes.scope(_scopes.KV_WRITE):
                kc = kc.at[b_idx, layer, t_idx].set(kq)
                vc = vc.at[b_idx, layer, t_idx].set(vq)
                ksc = c["k_scale"].at[b_idx, layer, t_idx].set(ks)
                vsc = c["v_scale"].at[b_idx, layer, t_idx].set(vs)
            with _scopes.scope(self._attn_scope):
                out = decode_attention(
                    q, kc[:, layer], vc[:, layer], lengths, scale=scale,
                    k_scales=ksc[:, layer], v_scales=vsc[:, layer])
            mut = (kc, vc, ksc, vsc) + (() if err is None else (err,))
            return (out,) + mut
        with _scopes.scope(_scopes.KV_WRITE):
            kc = kc.at[b_idx, layer, t_idx].set(k_new.astype(kc.dtype))
            vc = vc.at[b_idx, layer, t_idx].set(v_new.astype(vc.dtype))
        with _scopes.scope(self._attn_scope):
            out = decode_attention(q, kc[:, layer], vc[:, layer], lengths,
                                   scale=scale)
        return out, kc, vc

    def finalize(self, advance=None) -> SlottedKVCache:
        """``advance`` (per-slot int32, optional) overrides the uniform
        per-step advance — the speculative verify entry passes the
        ACCEPTED count + 1 so rejected drafts roll back in-program."""
        adv = (jnp.asarray(self._steps, jnp.int32) if advance is None
               else jnp.asarray(advance, jnp.int32))
        if self.active is not None:
            adv = adv * self.active.astype(jnp.int32)
        return SlottedKVCache(self.k, self.v, self.lengths + adv,
                              k_scale=self.k_scale, v_scale=self.v_scale)


class PrefillView(_CacheView):
    """Bucketed single-sequence prefill into one slot: input is
    ``(1, bucket)`` right-padded tokens with ``true_len`` real ones.
    Writes rows ``[0, bucket)`` of the (dynamic) ``slot`` via
    ``dynamic_update_slice`` and attends block-causally — pad rows
    compute garbage that is masked forever (``lengths[slot] = true_len``)
    and progressively overwritten by subsequent decode appends.  Int8
    caches quantize the written rows; the block attention itself runs on
    the exact pre-quantization K/V (nothing prior to attend to)."""

    _attn_scope = _scopes.PREFILL_ATTN

    def __init__(self, cache: SlottedKVCache, slot, true_len):
        super().__init__(cache)
        self.slot = jnp.asarray(_unwrap(slot), jnp.int32)
        self.true_len = jnp.asarray(_unwrap(true_len), jnp.int32)

    def position_ids(self, batch, seq_len):
        if batch != 1:
            raise ValueError("PrefillView is single-sequence (got batch=%d)"
                             % batch)
        return jnp.arange(seq_len, dtype=jnp.int32)[None, :]

    def _append_attend_raw(self, layer, carry, q, k_new, v_new, scale):
        from ..kernels import flash_attention as fa
        from ..nn.functional.attention import sdpa_reference_raw
        c = dict(zip(self.carry_fields(), carry))
        kc, vc = c["k"], c["v"]
        zero = jnp.zeros((), jnp.int32)
        start = (self.slot, jnp.asarray(layer, jnp.int32), zero, zero, zero)
        if self.quantized:
            kq, ks, vq, vs, _err = self._quantize_new(c, k_new, v_new)
        with _scopes.scope(_scopes.KV_WRITE):
            if self.quantized:
                kc = jax.lax.dynamic_update_slice(kc, kq[:, None], start)
                vc = jax.lax.dynamic_update_slice(vc, vq[:, None], start)
                ksc = jax.lax.dynamic_update_slice(
                    c["k_scale"], ks[:, None], start[:-1])
                vsc = jax.lax.dynamic_update_slice(
                    c["v_scale"], vs[:, None], start[:-1])
            else:
                kc = jax.lax.dynamic_update_slice(
                    kc, k_new.astype(kc.dtype)[:, None], start)
                vc = jax.lax.dynamic_update_slice(
                    vc, v_new.astype(vc.dtype)[:, None], start)
        # fresh slot: nothing precedes the block — attention is plain
        # causal over the bucket (bucket^2 logits, not bucket*max_len),
        # through the Pallas flash kernel when the shapes support it
        with _scopes.scope(self._attn_scope):
            if fa.supported(q, k_new):
                out = fa.flash_attention_bshd(q, k_new, v_new, causal=True,
                                              scale=scale)
            else:
                out = sdpa_reference_raw(q, k_new, v_new, None, 0.0, True,
                                         scale)
        if self.quantized:
            return out, kc, vc, ksc, vsc
        return out, kc, vc

    def finalize(self) -> SlottedKVCache:
        return SlottedKVCache(
            self.k, self.v, self.lengths.at[self.slot].set(self.true_len),
            k_scale=self.k_scale, v_scale=self.v_scale)


class PagedDecodeView(_CacheView):
    """Batched decode over the paged pool: q/k/v arrive as
    ``(num_slots, s, heads, head_dim)``; each slot's new tokens scatter
    into its mapped pages at rows ``lengths[b] + j`` and attention
    gathers only the slot's page-table row.  Unlike the slotted view,
    writes from INACTIVE lanes are dropped in-program (routed to an
    out-of-bounds page id): a retired slot's stale table row may point at
    pages the allocator has reassigned, so its lane must never write."""

    _extra_fields = ("page_table",)

    def __init__(self, cache: PagedKVCache, active=None, max_len=None,
                 track_quant_err=False, tp=1):
        super().__init__(cache, track_quant_err=track_quant_err)
        self.page_table = _unwrap(cache.page_table)
        self.active = None if active is None else _unwrap(active)
        # write/length cap: the engine's DECLARED max_len can be tighter
        # than the pool capacity when max_len % page_size != 0 — appends
        # at or past it drop and lengths stop advancing, matching the
        # slotted view's rows-past-max_len guard
        self.max_len = (int(max_len) if max_len is not None
                        else int(cache.max_len))
        # tensor-parallel degree of the enclosing sharded program: the
        # attention autotune key must price the PER-SHARD head count
        # (trace-time shapes are global under jit-with-sharding)
        self.tp = int(tp)
        self._steps = 0

    def position_ids(self, batch, seq_len):
        if batch != int(self.page_table.shape[0]):
            raise ValueError(
                "batched paged decode needs batch == num_slots (%d), got "
                "%d — use PagedPrefillChunkView for single sequences"
                % (self.page_table.shape[0], batch))
        return (self.lengths[:, None]
                + jnp.arange(seq_len, dtype=jnp.int32)[None, :])

    def _append_attend_raw(self, layer, carry, q, k_new, v_new, scale):
        from ..kernels.decode_attention import paged_decode_attention
        c = dict(zip(self.carry_fields(), carry))
        kc, vc, table, lengths = c["k"], c["v"], c["page_table"], \
            c["lengths"]
        s = int(q.shape[1])
        self._steps = s
        pos = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        valid = pos < jnp.asarray(self.max_len, jnp.int32)
        if self.active is not None:
            valid = valid & self.active[:, None]
        if self.quantized:
            kq, ks, vq, vs, err = self._quantize_new(c, k_new, v_new)
            kc, vc, ksc, vsc = paged_scatter(
                kc, vc, layer, table, pos, valid, kq, vq,
                ksc=c["k_scale"], vsc=c["v_scale"], ks_new=ks, vs_new=vs)
            with _scopes.scope(self._attn_scope):
                out = paged_decode_attention(
                    q, kc[:, layer], vc[:, layer], table, lengths,
                    scale=scale, k_scales=ksc[:, layer],
                    v_scales=vsc[:, layer], tp=self.tp)
            mut = (kc, vc, ksc, vsc) + (() if err is None else (err,))
            return (out,) + mut
        kc, vc, _, _ = paged_scatter(kc, vc, layer, table, pos, valid,
                                     k_new, v_new)
        with _scopes.scope(self._attn_scope):
            out = paged_decode_attention(q, kc[:, layer], vc[:, layer],
                                         table, lengths, scale=scale,
                                         tp=self.tp)
        return out, kc, vc

    def finalize(self, advance=None) -> PagedKVCache:
        """``advance`` (per-slot int32, optional) overrides the uniform
        per-step advance — the speculative verify entry passes the
        ACCEPTED count + 1, rolling rejected drafts' length advance (and
        so their tail-page rows, overwritten by the next append) back
        in-program."""
        adv = (jnp.asarray(self._steps, jnp.int32) if advance is None
               else jnp.asarray(advance, jnp.int32))
        if self.active is not None:
            adv = adv * self.active.astype(jnp.int32)
        return PagedKVCache(self.k, self.v, self.page_table,
                            jnp.minimum(self.lengths + adv,
                                        jnp.asarray(self.max_len,
                                                    jnp.int32)),
                            declared_max_len=self.max_len,
                            k_scale=self.k_scale, v_scale=self.v_scale)


class PagedPrefillChunkView(_CacheView):
    """One fixed-size prefill chunk of one slot's prompt: input is
    ``(1, chunk)`` right-padded tokens, ``n_valid`` of them real, at
    global positions ``n_before + j``.  Writes land in the slot's mapped
    pages (the engine allocates them host-side before the chunk runs);
    padding writes are dropped in-program.  Attention gathers the slot's
    page-table row and masks ``t <= n_before + j`` — the full mapped
    past (shared prefix pages included) plus the chunk's own causal
    band, so a chunk after a prefix-cache hit attends to pages it never
    computed.  Int8 caches quantize the chunk's writes; its attention
    reads back through the dequantizing gather (the chunk attends its
    own quantized rows — the same values every later decode step sees)."""

    _extra_fields = ("page_table",)
    _attn_scope = _scopes.PREFILL_ATTN

    def __init__(self, cache: PagedKVCache, slot, n_before, n_valid,
                 tp=1):
        super().__init__(cache)
        self.page_table = _unwrap(cache.page_table)
        self.slot = jnp.asarray(_unwrap(slot), jnp.int32)
        self.n_before = jnp.asarray(_unwrap(n_before), jnp.int32)
        self.n_valid = jnp.asarray(_unwrap(n_valid), jnp.int32)
        self.declared_max_len = cache.declared_max_len
        self.tp = int(tp)    # per-shard autotune keys (PagedDecodeView)

    def position_ids(self, batch, seq_len):
        if batch != 1:
            raise ValueError(
                "PagedPrefillChunkView is single-sequence (got batch=%d)"
                % batch)
        return (self.n_before
                + jnp.arange(seq_len, dtype=jnp.int32))[None, :]

    def _append_attend_raw(self, layer, carry, q, k_new, v_new, scale):
        from ..kernels.decode_attention import paged_decode_attention
        c = dict(zip(self.carry_fields(), carry))
        kc, vc, table = c["k"], c["v"], c["page_table"]
        C = int(q.shape[1])
        max_pages = int(table.shape[1])
        row_tab = jax.lax.dynamic_slice(
            table, (self.slot, jnp.zeros((), jnp.int32)), (1, max_pages))
        j = jnp.arange(C, dtype=jnp.int32)
        pos = (self.n_before + j)[None, :]
        valid = (j < self.n_valid)[None, :]
        if self.quantized:
            kq, ks, vq, vs, _err = self._quantize_new(c, k_new, v_new)
            kc, vc, ksc, vsc = paged_scatter(
                kc, vc, layer, row_tab, pos, valid, kq, vq,
                ksc=c["k_scale"], vsc=c["v_scale"], ks_new=ks, vs_new=vs)
            with _scopes.scope(self._attn_scope):
                out = paged_decode_attention(
                    q, kc[:, layer], vc[:, layer], row_tab,
                    self.n_before[None], scale=scale,
                    k_scales=ksc[:, layer], v_scales=vsc[:, layer],
                    tp=self.tp)
            return out, kc, vc, ksc, vsc
        kc, vc, _, _ = paged_scatter(kc, vc, layer, row_tab, pos, valid,
                                     k_new, v_new)
        with _scopes.scope(self._attn_scope):
            out = paged_decode_attention(q, kc[:, layer], vc[:, layer],
                                         row_tab, self.n_before[None],
                                         scale=scale, tp=self.tp)
        return out, kc, vc

    def finalize(self) -> PagedKVCache:
        return PagedKVCache(
            self.k, self.v, self.page_table,
            self.lengths.at[self.slot].set(self.n_before + self.n_valid),
            declared_max_len=self.declared_max_len,
            k_scale=self.k_scale, v_scale=self.v_scale)
