"""The TPU-native decode engine: static-shape KV cache + a batched
decode step that compiles exactly once.

Two cache layouts (``paged=True`` is the default — ISSUE 7):

* **Paged** — a fixed pool of fixed-size KV pages plus a per-slot int32
  page table (:class:`~.cache.PagedKVCache` + the host-side
  :class:`~.pages.PageAllocator`).  Compiled entry points:

  - ``decode`` — ALL slots advance one token in one fixed-shape
    program: scatter-append into each slot's tail page, paged-gather
    length-masked attention (``kernels.decode_attention`` family
    ``decode_attn_paged``), per-slot sampling.  Compiles ONCE.
  - ``spec_verify`` (``spec_k > 0`` — ISSUE 8) — the speculative
    **batched verify**: each slot's iteration input is ``k + 1`` tokens
    (the last committed token plus ``k`` host-side prompt-lookup
    drafts, :mod:`.spec`), ONE forward scores all positions over the
    paged cache, and the standard accept/resample rule
    (:func:`~.sampling.spec_accept`) runs in-program: rejected drafts
    roll the per-slot length counters (and with them the tail-page
    rows, overwritten by the next append) back INSIDE the program — no
    host sync on the hot path.  Fixed ``k`` means this is ONE static
    program (watchdog budget 1) beside the single-token ``decode``
    fallback; accept-rate extremes change traced values, never the
    program.  Greedy output is bit-identical to non-speculative decode;
    temperature sampling consumes exactly ONE threaded key per
    iteration regardless of accepted count (PR 7's seed-reproducibility
    contract).
  - ``prefill_chunk`` — one fixed-size chunk of one slot's prompt:
    admitting a long prompt runs ``ceil(n / chunk)`` iterations of this
    ONE program, interleaved by the scheduler with live decode steps so
    a long admission can no longer stall in-flight TPOT.  The final
    chunk samples the first generated token.
  - ``cow_copy`` — copy one page (all layers, scale rows included) to a
    fresh page: the copy-on-write step that un-shares a prefix page
    before a write.

  **Prefix sharing**: prompt pages are content-hashed at admission; a
  hit maps the slot's leading page-table entries to existing refcounted
  pages instead of recomputing/storing them.  Sharing is capped at
  ``n - 1`` tokens so the final token always runs through the chunk
  program (producing the first-token logits); a fully-cached prompt
  admits in ONE 1-token chunk, whose write copy-on-writes the shared
  tail page.

* **Slotted** (``paged=False`` — the PR-5 layout, kept for A/B and
  parity): per-slot contiguous ``max_len`` buffers, bucketed whole-
  prompt prefill.

**Tensor-parallel sharded decode (``tp=N`` — ISSUE 12).**  The paged
engine decodes MULTI-CHIP: the KV pool (codes AND the int8 scale pools)
is partitioned over the HEADS axis of a private ``('mp',)`` mesh, the
model parameters carry their Megatron pspec annotations (qkv/fc1
column-, out/fc2 row-, embeddings vocab-sharded — the SAME machinery
the training TP path uses, ``distributed/mp_layers.py``), and every
jitted entry (decode, prefill_chunk, cow_copy, spec_verify) becomes its
sharded twin via ``jax.jit`` with in/out shardings — GSPMD inserts
exactly the collectives the training path gets (psum after the
row-parallel matmuls, the vocab-parallel logits gather), audited by
TPU503 on the lowered sharded entries.  Page table, lengths, tokens and
the whole sampling state stay REPLICATED; the host-side bookkeeping
(:class:`~.pages.PageAllocator`, the length mirror) is untouched —
sharding divides bytes, never meaning.  Donation stays intact (TPU502:
the sharded pool aliases input→output per shard), the compile-once
discipline holds (ONE sharded program per entry across slot churn,
prefix hits, chunked admissions and spec verify), and per-chip decode
KV bytes/token drop to ``1/tp`` of the single-chip bound
(``kv_row_bytes``/``kv_pool_bytes``/``kv_bytes_per_token`` all report
PER-SHARD truth).  ``tp=1`` (the default) is byte-identical to the
unsharded engine.

**Decomposed collective overlap (``overlap_comm`` — ISSUE 20).**  On a
tp>1 engine, ``overlap_comm=True`` (or ``PADDLE_TPU_MP_OVERLAP=1``;
explicit ``False`` pins it off) traces the sharded entries under
:mod:`~paddle_tpu.distributed.mp_overlap`'s scope: the per-layer
monolithic all-gather / all-reduce / all-to-all islands become chunked
``ppermute`` rings interleaved with the partial matmuls, so on real
ICI the transfer hides behind compute.  Same math, different schedule
— at tp=2 every partial sum has exactly two f32 terms and greedy
output is BIT-identical to the monolithic engine (test-asserted).
The switch is engine geometry: ``engine_for`` folds the resolved value
into its cache key, and the structural claim (zero monolithic
all-gathers, permute chain present) is auditable per-kind via
``observability.costs.collective_stats``.

**int8 KV cache (``kv_dtype="int8"`` — ISSUE 8).**  Either layout can
store the pool as int8 codes + per-(row, head) f32 scales
(:mod:`.cache`): appends quantize in-program, the attention families'
q8 variants dequantize inline in the gather, and decode KV HBM traffic
per row drops from ``head_dim * dtype_bytes`` to ``head_dim + 4`` —
about HALF the bf16 pool's read bound at head_dim 64
(``kv_bytes_per_token()`` accounts codes + scales honestly).  Composes
with speculative decode: the verify program runs the same q8 gather.
Opt-in ``PADDLE_TPU_METRICS_KV_QUANT_ERROR=1`` (at engine construction)
threads a max-abs-dequant-error accumulator through the decode/verify
entries and publishes the ``serving.kv_quant_error`` gauge (one device
sync per step, same caveat as ``train.grad_norm``).
``kv_dtype="fp8"`` (ISSUE 20) runs float8_e4m3fn codes through the
SAME codes+scales plumbing — identical 1-byte row accounting, an
amax/448 saturating grid in :func:`.cache.quantize_kv`, and the
canonical dtype string (``"float8_e4m3fn"``) in the autotune key and
flight dump.

Every argument that varies across steps (tokens, draft tokens, active
mask, sampling parameters, PRNG key, page table, lengths) is a traced
array — nothing retraces, ever; asserted by ``decode_compile_count``/
``verify_compile_count`` and the recompile watchdog.  All entries
**donate the cache buffers** (code pools AND scale pools): XLA aliases
them input→output, so the multi-hundred-MB pool is updated in place
instead of double-buffered (TPU502 audits that the aliasing actually
materializes — see ``analysis/trace/programs.py``'s ``serving``
builder).  The page table is a per-step *input* (host-owned, re-uploaded
only when it changes), not donated.

The engine is deliberately request-free: slot admission/eviction policy
lives in :mod:`.scheduler`; the engine only refuses page allocation
(:class:`~.pages.PagePoolExhausted`) and lets the scheduler pick a
victim.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.dtype import x64_scope
from ..core.tensor import Tensor
from ..distributed import mesh as _mesh
from ..distributed import mp_overlap as _mp_overlap
from ..distributed.mp_layers import MP_AXIS
from ..observability import flight as _flight
from ..observability import registry as _metrics
from ..observability import tracing as _tracing
from . import cache as _cache_mod
from .cache import (DecodeView, PagedDecodeView, PagedKVCache,
                    PagedPrefillChunkView, PrefillView, SlottedKVCache,
                    _unwrap)
from .pages import PageAllocator, PagePoolExhausted
from .sampling import TOP_K_MAX, sample, spec_accept

__all__ = ["DecodeEngine", "InflightDecode", "PagePoolExhausted",
           "PrefillTask", "prefill_buckets_for"]


class _DispatchSpan:
    """See :meth:`DecodeEngine._dispatch_span`."""

    __slots__ = ("_annotation", "_tracer", "_phase", "_entry", "_span",
                 "_c0")

    def __init__(self, tracer, phase, entry):
        self._annotation = _tracing.annotation("engine", phase)
        self._tracer, self._phase, self._entry = tracer, phase, entry

    def __enter__(self):
        self._annotation.__enter__()
        # NOOP_SPAN by identity while the tracer is off
        self._span = self._tracer.span("engine." + self._phase)
        if self._span is not _tracing.NOOP_SPAN:
            self._c0 = int(self._entry.compile_count)
        return self

    def __exit__(self, *exc):
        if self._span is not _tracing.NOOP_SPAN:
            c1 = int(self._entry.compile_count)
            self._span.end(compile_count=c1, compiles=c1 - self._c0)
        return self._annotation.__exit__(*exc)


def prefill_buckets_for(max_len, min_bucket=16):
    """Power-of-two prefill buckets up to ``max_len`` (slotted mode); a
    non-power-of-two ``max_len`` is appended as the final bucket so every
    prompt that fits the cache has a bucket."""
    out = []
    b = min(int(min_bucket), int(max_len))
    while b <= int(max_len):
        out.append(b)
        b *= 2
    if not out or out[-1] < int(max_len):
        out.append(int(max_len))
    return out


@contextlib.contextmanager
def _eval_scope(model):
    """Run the engine's compiled entries with the model in eval mode but
    RESTORE the caller's mode after: generate() between training epochs
    must not silently disable dropout for the rest of the run (mode only
    matters at trace time, but the flip would otherwise leak out)."""
    was_training = bool(getattr(model, "training", False))
    model.eval()
    try:
        yield
    finally:
        if was_training:
            model.train()


@dataclasses.dataclass
class InflightDecode:
    """One dispatched, not-yet-consumed decode (or speculative verify)
    step — the handle the overlapped scheduler loop holds while the
    device runs the step and the host does the *previous* step's
    bookkeeping.  Every field except ``active`` is a device array
    (a future under jax's async dispatch): nothing here has forced a
    host sync yet.  ``decode_fetch``/``decode_spec_fetch`` consume it —
    the ONLY blocking point of an engine iteration."""
    kind: str                             # "decode" | "spec"
    active: "np.ndarray"                  # dispatch-time mask (host copy)
    tok: object = None                    # (S,) int32 next tokens (decode)
    emitted: object = None                # (S, k+1) int32 (spec)
    counts: object = None                 # (S,) int32 accepted+1 (spec)
    logits: object = None                 # last-position logits
    qerr: object = None                   # opt-in quant-error scalar
    paged_rows: int = 0                   # dispatch-time mapped-rows
    consumed: bool = False                # set by the fetch
    slot_epoch: object = None             # per-slot free-epoch at
                                          # dispatch (spec only): the
                                          # fetch advances the length
                                          # mirror ONLY for lanes not
                                          # freed/readmitted since


@dataclasses.dataclass
class PrefillTask:
    """Host-side state of one in-flight chunked admission."""
    slot: int
    ids: "np.ndarray"                     # the full prompt, int32
    pos: int                              # next position to compute
    temperature: float
    top_k: int
    top_p: float
    shared_tokens: int = 0                # prefix-cache coverage (capped)
    shared_pages: int = 0                 # pages mapped instead of computed
    chunks_run: int = 0
    done: bool = False
    first_token: int = -1                 # sampled by the FINAL chunk
    first_token_dev: object = None        # () device array (sync=False)
    last_logits: object = None            # (vocab,) device array


class DecodeEngine:
    """Compiled serving engine for a causal-LM Layer (``model(input_ids,
    cache=<view>) -> (logits, cache)`` with a ``config`` carrying the
    GPT geometry — :class:`paddle_tpu.models.gpt.GPTForCausalLM`)."""

    def __init__(self, model, num_slots=4, max_len=None, cache_dtype=None,
                 min_bucket=16, seed=0, top_k_max=TOP_K_MAX, donate=True,
                 paged=True, page_size=64, num_pages=None,
                 prefill_chunk=None, kv_dtype=None, spec_k=0,
                 spec_ngram=3, tracer=None, tp=1, device=None,
                 handoff_pages=4, kv_host_bytes=None, overlap_comm=None):
        cfg = model.config
        self.model = model
        # request-scoped tracing (ISSUE 9): the engine lane carries one
        # dispatch span per compiled-entry call with the watchdog's
        # compile-count delta; the no-op default costs one bool check
        self._tracer = (tracer if tracer is not None
                        else _tracing.default_tracer())
        self.num_slots = int(num_slots)
        self.max_len = int(max_len or cfg.max_position_embeddings)
        if self.max_len > cfg.max_position_embeddings:
            raise ValueError(
                "max_len %d exceeds the model's position budget %d"
                % (self.max_len, cfg.max_position_embeddings))
        self.top_k_max = int(top_k_max)
        self.paged = bool(paged)
        self.state = model.functional_state()
        # the UNSHARDED snapshot leaves, kept for refresh_state's
        # param-change identity test: tp engines replace self.state with
        # device_put COPIES, so comparing fresh functional_state leaves
        # against self.state would read "changed" on every cached-engine
        # reuse — silently dropping the prefix cache and re-uploading
        # the whole parameter tree per generate() round
        self._state_src_leaves = jax.tree_util.tree_leaves(self.state)
        if cache_dtype is None:
            # match the activation dtype: the embedding weight's dtype is
            # what the residual stream (and so K/V) runs in
            probe = getattr(getattr(model, "gpt", model), "wte", None)
            cache_dtype = (jnp.dtype(probe.weight._array.dtype)
                           if probe is not None
                           else jnp.dtype(next(iter(self.state.values()
                                                    )).dtype))
        self._heads = cfg.num_attention_heads
        self._head_dim = cfg.hidden_size // cfg.num_attention_heads
        self._layers = cfg.num_hidden_layers
        self._cache_dtype = jnp.dtype(cache_dtype)
        # canonicalize through the cache's own gate so the engine, the
        # pool, and the autotune key can never disagree on the code
        # dtype ("fp8" shorthand included — ISSUE 20)
        _code_dt, _ = _cache_mod._as_kv_dtypes(kv_dtype)
        self.kv_dtype = (_code_dt if _code_dt is not None
                         else self._cache_dtype)
        self._quantized = _code_dt is not None
        # opt-in quant-error gauge: the flag is read ONCE here — it
        # changes the traced entries (an extra carried scalar + output),
        # so toggling the env var mid-process must not retrace
        self._track_qerr = bool(self._quantized and os.environ.get(
            "PADDLE_TPU_METRICS_KV_QUANT_ERROR", "0") == "1")
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        if self.spec_k and not self.paged:
            raise ValueError(
                "speculative decode runs on the paged engine (spec_k "
                "with paged=False is not supported — the slotted layout "
                "is the A/B baseline)")
        if self.spec_k >= self.max_len:
            raise ValueError("spec_k %d must be < max_len %d"
                             % (self.spec_k, self.max_len))
        # -- tensor parallelism (ISSUE 12) ---------------------------------
        self.tp = int(tp)
        if self.tp < 1:
            raise ValueError("tp must be >= 1")
        if self.tp > 1 and not self.paged:
            raise ValueError(
                "tensor-parallel decode runs on the paged engine (tp > 1 "
                "with paged=False is not supported — the slotted layout "
                "is the single-chip A/B baseline)")
        self.mesh = None
        self._param_shard_specs = {}
        self._entry_shardings = {}
        if device is not None and self.tp > 1:
            raise ValueError(
                "device= pins a SINGLE-chip engine; tp > 1 engines pick "
                "their own devices (the first tp of jax.devices())")
        if device is not None and not self.paged:
            raise ValueError(
                "device= runs on the paged engine (the slotted layout "
                "is the single-chip A/B baseline)")
        if self.tp > 1:
            devices = jax.devices()
            if len(devices) < self.tp:
                raise ValueError(
                    "tp=%d needs %d devices, have %d (CPU: set XLA_FLAGS="
                    "--xla_force_host_platform_device_count before the "
                    "backend initializes)"
                    % (self.tp, self.tp, len(devices)))
            if self._heads % self.tp:
                raise ValueError(
                    "tp=%d must divide num_attention_heads=%d (the KV "
                    "pool is partitioned over heads)"
                    % (self.tp, self._heads))
            # a PRIVATE single-axis mesh over the first tp devices — the
            # engine never mutates the process-global mesh; its traced
            # calls install this one via mesh_scope so the model's
            # with_sharding_constraint sites (incl. the head constraints
            # in the cache walk) resolve the serving topology
            self.mesh = Mesh(np.asarray(devices[:self.tp]), (MP_AXIS,))
        elif device is not None:
            # device pinning (ISSUE 15): a 1-device ('mp',) mesh commits
            # the pool, the parameters, and every entry's outputs to the
            # GIVEN device through the same jit-with-shardings machinery
            # the tp path uses (single-device jit outputs are uncommitted
            # — re-checked on jax 0.9.0 — so "create the buffers there"
            # would not survive the first call) — role-split disaggregated
            # serving
            # places its prefill engine on its own chip this way
            self.mesh = Mesh(np.asarray([device]), (MP_AXIS,))
        # -- collective–matmul overlap (ISSUE 20) --------------------------
        # resolved ONCE at construction (arg > scope > PADDLE_TPU_MP_OVERLAP
        # env) and pinned into every entry trace via _trace_scope, so the
        # compiled programs can never flip lowering mid-process.  Only
        # meaningful on a tp>1 mesh: the rings need a >=2-device 'mp' axis.
        self.overlap_comm = bool(_mp_overlap.enabled(overlap_comm)
                                 and self.tp > 1)
        if self.mesh is not None:
            self._param_shard_specs = self._collect_param_specs()
            self.state = self._shard_state(self.state)
        self._base_key = jax.random.key(int(seed))
        self._rng_step = 0
        # metric handles, fetched once (no-op singletons when disabled)
        self._m_pool = _metrics.gauge("serving.page_pool_used")
        self._m_cow = _metrics.counter("serving.cow_copies")
        self._m_qerr = _metrics.gauge("serving.kv_quant_error")
        self._m_tp = _metrics.gauge("serving.tp_degree")
        self._m_tp.set(self.tp)
        self._m_coll = _metrics.counter("serving.collective_bytes")
        # opt-in per-step collective-bytes accounting: priced ONCE per
        # entry from the compiled sharded program's HLO (an extra AOT
        # compile on first use — grad_norm-style env opt-in, read once)
        self._track_coll = bool(
            self.tp > 1 and os.environ.get(
                "PADDLE_TPU_METRICS_COLLECTIVES", "0") == "1")
        self._coll_price = {}
        # decode KV-read accounting (the bench's kv_bytes_per_token A/B):
        # per decode/verify step, `paged_rows` accrues the rows a
        # length-aware paged schedule reads (mapped pages, ONE sweep per
        # step however many tokens the step commits) vs `flat_rows`, the
        # slotted slots*max_len PER-TOKEN bound — so speculative steps
        # show the read amortization and int8 halves the per-row cost
        # (row_bytes accounts codes + scales)
        self.kv_stats = {"tokens": 0, "paged_rows": 0, "flat_rows": 0}
        # speculative accounting: steps = verify iterations, proposed =
        # k per active lane, accepted = accepted draft tokens (the
        # bench's accepted_tokens_per_step = accepted/steps — the EXTRA
        # tokens per verify iteration beyond the baseline one-per-slot)
        self.spec_stats = {"steps": 0, "proposed": 0, "accepted": 0}
        if self.paged:
            self._init_paged(cfg, page_size, num_pages, prefill_chunk,
                             donate, handoff_pages)
        else:
            self._init_slotted(cfg, min_bucket, donate)
        # tiered KV host cache (ISSUE 17): a bounded host-RAM LRU behind
        # the device pool.  Reclaimed (or explicitly cold) refcount-0
        # cached pages spill through kv_export; a later hash-hit
        # admission that misses the device cache pulls them back through
        # kv_import.  Off unless a budget is given (param wins over the
        # PADDLE_TPU_KV_HOST_BYTES env).
        self._host_tier = None
        self._kv_index = None     # ClusterPrefixIndex, attach_cluster_index
        self._spill_buf = None    # spill's OWN persistent export buffer:
                                  # the handoff buffer may be mid-transfer
                                  # (staged but not yet imported) when a
                                  # reclaim fires inside _alloc_dst, and
                                  # re-donating it would tear the splice
        self._m_host_bytes = _metrics.gauge("serving.kv_host_bytes")
        self._m_host_misses = _metrics.counter("serving.kv_host_misses")
        self._m_host_spill = _metrics.counter(
            "serving.kv_host_spilled_pages")
        if self.paged:
            from .kv_tier import HostPageTier, host_bytes_default
            budget = (int(kv_host_bytes) if kv_host_bytes is not None
                      else host_bytes_default())
            if budget > 0:
                self._host_tier = HostPageTier(budget)
                self._alloc.spill_hook = self._spill_page
        # black-box flight recorder: dumps collect this engine's state
        # summary (weakref — registration never pins the engine); the
        # HBM ledger prices this engine's KV pool the same way
        _flight.register_engine(self)
        from ..observability import hbm as _hbm
        _hbm.register_engine(self)

    def _kv_dtype_arg(self):
        # canonical dtype string ("int8" / "float8_e4m3fn") — the cache
        # gate and the autotune keys both parse it back via jnp.dtype
        return str(self.kv_dtype) if self._quantized else None

    def _cache_scale_args(self):
        return (self.cache.k_scale, self.cache.v_scale)

    # ------------------------------------------------------------------
    # tensor-parallel sharding (ISSUE 12) — tp=1 engines never enter any
    # of these paths; tp>1 is paged-only (validated in __init__)
    # ------------------------------------------------------------------

    def _collect_param_specs(self):
        """{state name: PartitionSpec} from the parameters' Megatron
        pspec annotations (``distributed/mp_layers.py`` layouts baked
        into ``models/gpt.py``), filtered to the serving mesh's axes —
        training annotations also name dp/sep axes this single-purpose
        ('mp',) mesh does not carry.  A pspec IS one PartitionSpec, not
        a tuple of them (the TrainStep lesson).  Raises on a sharded dim
        the TP degree does not divide: GSPMD would reject the uneven
        NamedSharding at dispatch anyway, but this names the parameter."""
        axis_names = set(self.mesh.axis_names)
        specs = {}
        for name, t in self.model.state_dict().items():
            spec = getattr(t, "pspec", None)
            if spec is None:
                specs[name] = PartitionSpec()
                continue
            kept = []
            for el in tuple(spec):
                if isinstance(el, str):
                    kept.append(el if el in axis_names else None)
                elif isinstance(el, (tuple, list)):
                    sub = tuple(a for a in el if a in axis_names)
                    kept.append(sub if sub else None)
                else:
                    kept.append(None)
            for dim, el in enumerate(kept):
                if el is not None and t.shape[dim] % self.tp:
                    raise ValueError(
                        "parameter %r dim %d (size %d) is mp-sharded "
                        "but not divisible by tp=%d"
                        % (name, dim, int(t.shape[dim]), self.tp))
            specs[name] = PartitionSpec(*kept)
        return specs

    def _sh(self, *spec):
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def _state_shardings(self):
        return {k: NamedSharding(self.mesh, self._param_shard_specs[k])
                for k in self.state}

    def _shard_state(self, state):
        """Place a freshly snapshotted parameter tree onto the serving
        mesh per its pspec annotations.  Required, not cosmetic: after
        training, ``functional_state`` leaves are committed to their
        training placement, and feeding them to the sharded entries'
        ``in_shardings`` raises a device-assignment mismatch instead of
        silently resharding (the ``refresh_state`` regression).  The
        identity for meshless engines; device-pinned (1-device mesh)
        engines place the tree on their device the same way."""
        if self.mesh is None:
            return state
        sh = {k: NamedSharding(self.mesh, self._param_shard_specs[k])
              for k in state}
        return {k: jax.device_put(v, sh[k]) for k, v in state.items()}

    def _jit_kwargs(self, entry):
        """The sharding kwargs a given entry's jit (and any AOT re-jit
        that must price the SAME program — ``cost_reports``) carries:
        one definition so the served and the priced program can never
        drift."""
        if entry not in self._entry_shardings:
            return {}
        ins, outs = self._entry_shardings[entry]
        return dict(in_shardings=ins, out_shardings=outs)

    def _trace_scope(self):
        """Mesh context for the compiled entries' traced calls: the
        model's with_sharding_constraint sites — incl. the head
        constraints on the cache walk — must resolve the SERVING
        topology, whatever the process-global mesh is.  tp=1 engines
        install ``None`` (not a no-op!): a leftover TRAINING mesh
        declaring 'mp' would otherwise turn the single-chip decode
        trace into an SPMD program over the training devices — the
        'tp=1 is byte-identical to the unsharded engine' contract must
        hold in mesh-laden processes too.  The overlap switch is pinned
        the same way: an engine built with overlap_comm=False stays
        monolithic even if PADDLE_TPU_MP_OVERLAP flips on later (and
        vice versa) — retraces always reproduce the first lowering."""
        return self._entry_scope()

    @contextlib.contextmanager
    def _entry_scope(self):
        with _mesh.mesh_scope(self.mesh), \
                _mp_overlap.overlap_scope(self.overlap_comm):
            yield

    def _collective_price(self, entry):
        """Collective bytes ONE step of ``entry`` moves over the mesh,
        priced lazily from the compiled sharded program's partitioned
        HLO (``observability.costs.collective_stats``) and cached — the
        per-step counter increments by this constant."""
        price = self._coll_price.get(entry)
        if price is None:
            from ..observability import costs as _costs
            report = self.cost_reports(only=(entry,))[entry]
            price = int(report.collective_bytes or 0)
            self._coll_price[entry] = price
        return price

    # ------------------------------------------------------------------
    # slotted mode (PR 5 layout — kept for A/B and parity)
    # ------------------------------------------------------------------

    def _init_slotted(self, cfg, min_bucket, donate):
        self.buckets = prefill_buckets_for(self.max_len, min_bucket)
        self.prompt_cap = self.buckets[-1]
        model, k_max = self.model, self.top_k_max
        track_qerr = self._track_qerr
        self.cache = SlottedKVCache.create(
            self.num_slots, self._layers, self.max_len, self._heads,
            self._head_dim, self._cache_dtype,
            kv_dtype=self._kv_dtype_arg())

        def decode_fn(state, cache_k, cache_v, k_scale, v_scale, lengths,
                      tokens, active, key, temps, top_ks, top_ps):
            """One batched decode iteration over every slot."""
            model.eval()   # trace-time: cached decode is inference-only
            view = DecodeView(
                SlottedKVCache(cache_k, cache_v, lengths,
                               k_scale=k_scale, v_scale=v_scale),
                active=active, track_quant_err=track_qerr)
            from ..jit import functional_call
            (logits, _), _ = functional_call(model, state, Tensor(tokens),
                                             cache=view)
            logits = logits[:, -1, :]
            next_tok = sample(logits, key, temps, top_ks, top_ps, k_max)
            out = view.finalize()
            return (next_tok, logits, out.k, out.v, out.k_scale,
                    out.v_scale, out.lengths, view.quant_err)

        def prefill_fn(state, tokens, slot, true_len, cache_k, cache_v,
                       k_scale, v_scale, lengths, key, temp, top_k,
                       top_p):
            """Prefill one bucketed sequence into ``slot`` and sample the
            first generated token from the last REAL position."""
            model.eval()
            view = PrefillView(
                SlottedKVCache(cache_k, cache_v, lengths,
                               k_scale=k_scale, v_scale=v_scale),
                slot, true_len)
            from ..jit import functional_call
            (logits, _), _ = functional_call(model, state, Tensor(tokens),
                                             cache=view)
            last = jax.lax.dynamic_slice(
                logits, (jnp.zeros((), jnp.int32),
                         true_len - jnp.ones((), jnp.int32),
                         jnp.zeros((), jnp.int32)),
                (1, 1, logits.shape[-1]))[:, 0, :]
            tok = sample(last, key, temp[None], top_k[None], top_p[None],
                         k_max)[0]
            out = view.finalize()
            return (tok, last[0], out.k, out.v, out.k_scale, out.v_scale,
                    out.lengths)

        # hooks for the trace-tier audit (TPU501-505): the registry lowers
        # the un-jitted fns with keep_unused=True at these donate_argnums
        q = self._quantized
        self._decode_fn = decode_fn
        self._decode_donate_argnums = \
            ((1, 2, 5) + ((3, 4) if q else ())) if donate else ()
        self._prefill_fn = prefill_fn
        self._prefill_donate_argnums = \
            ((4, 5, 8) + ((6, 7) if q else ())) if donate else ()
        # recompile watchdog (observability.watchdog): decode is the
        # compile-ONCE entry — a second program is PR 5's silent-retrace
        # bug class and warns (raises under PADDLE_TPU_STRICT_COMPILE=1);
        # prefill's budget is its bucket count
        from ..observability.watchdog import watch
        self._decode = watch(
            "serving.decode",
            jax.jit(decode_fn, donate_argnums=self._decode_donate_argnums),
            expected=1)
        self._prefill = watch(
            "serving.prefill",
            jax.jit(prefill_fn,
                    donate_argnums=self._prefill_donate_argnums),
            expected=len(self.buckets))

    # ------------------------------------------------------------------
    # paged mode (ISSUE 7 layout — the default)
    # ------------------------------------------------------------------

    def _init_paged(self, cfg, page_size, num_pages, prefill_chunk,
                    donate, handoff_pages=4):
        self.page_size = min(int(page_size), self.max_len)
        self.max_pages = -(-self.max_len // self.page_size)
        # default pool: capacity parity with the slotted layout (every
        # slot can reach max_len).  Size it SMALLER to actually save
        # memory when typical lengths are short / prefixes shared.
        self.num_pages = int(num_pages if num_pages is not None
                             else self.num_slots * self.max_pages)
        self.prefill_chunk = int(prefill_chunk if prefill_chunk is not None
                                 else min(64, self.max_len))
        self.prompt_cap = self.max_len
        # disaggregated prefill/decode handoff (ISSUE 15): pages move
        # between role-split engines' pools through ONE fixed-size
        # transfer buffer of `handoff_pages` pages — a fixed chunk shape
        # keeps kv_export/kv_import each a single static program, and
        # the scheduler interleaves chunks between decode steps
        self.handoff_pages = max(1, min(int(handoff_pages),
                                        self.max_pages))
        self._handoff_buf = None       # lazily allocated, donated in
                                       # place by every kv_export call
        self._alloc = PageAllocator(self.num_pages, self.num_slots,
                                    self.max_pages, self.page_size,
                                    tracer=self._tracer)
        self._len_host = np.zeros((self.num_slots,), np.int64)
        # bumped by free_slot(): a speculative verify step consumed
        # AFTER its lane was freed (the overlapped loop's overshoot
        # step) must not advance the zeroed mirror — the in-program
        # advance landed in pages that free_slot already reclaimed
        self._slot_epoch = np.zeros((self.num_slots,), np.int64)
        self.cache = PagedKVCache.create(
            self.num_pages, self._layers, self.page_size, self._heads,
            self._head_dim, self.num_slots, self.max_pages,
            self._cache_dtype, kv_dtype=self._kv_dtype_arg())
        if self.mesh is not None:
            # the pool lives HEAD-SHARDED from birth: each chip holds
            # 1/tp of the KV bytes (the whole point), and the sharded
            # entries' donated aliasing needs matching input placement.
            # A device-pinned engine (1-device mesh) takes the same path
            # — 'sharding' there just means committed placement.
            c = self.cache
            pool = self._sh(None, None, None, MP_AXIS, None)
            scale = self._sh(None, None, None, MP_AXIS)
            rep = self._sh()
            self.cache = PagedKVCache(
                jax.device_put(c.k, pool), jax.device_put(c.v, pool),
                jax.device_put(c.page_table, rep),
                jax.device_put(c.lengths, rep),
                k_scale=(None if c.k_scale is None
                         else jax.device_put(c.k_scale, scale)),
                v_scale=(None if c.v_scale is None
                         else jax.device_put(c.v_scale, scale)))
        # hoist everything the traced closures need: capturing `self`
        # would pin the whole engine (buffers included) to the jitted fns
        model, k_max, L_max = self.model, self.top_k_max, self.max_len
        track_qerr = self._track_qerr
        quantized = self._quantized
        tp_deg = self.tp

        def decode_fn(state, cache_k, cache_v, k_scale, v_scale, lengths,
                      page_table, tokens, active, key, temps, top_ks,
                      top_ps):
            """One batched decode iteration over every slot (paged)."""
            model.eval()
            view = PagedDecodeView(
                PagedKVCache(cache_k, cache_v, page_table, lengths,
                             k_scale=k_scale, v_scale=v_scale),
                active=active, max_len=L_max, track_quant_err=track_qerr,
                tp=tp_deg)
            from ..jit import functional_call
            (logits, _), _ = functional_call(model, state, Tensor(tokens),
                                             cache=view)
            logits = logits[:, -1, :]
            next_tok = sample(logits, key, temps, top_ks, top_ps, k_max)
            out = view.finalize()
            return (next_tok, logits, out.k, out.v, out.k_scale,
                    out.v_scale, out.lengths, view.quant_err)

        def verify_fn(state, cache_k, cache_v, k_scale, v_scale, lengths,
                      page_table, tokens, active, key, temps, top_ks,
                      top_ps):
            """The speculative batched verify: ``tokens: (slots, k+1)``
            = [last committed token, draft_1..draft_k].  ONE forward
            scores every position; accept/resample and the rejected-
            draft length rollback run in-program."""
            model.eval()
            view = PagedDecodeView(
                PagedKVCache(cache_k, cache_v, page_table, lengths,
                             k_scale=k_scale, v_scale=v_scale),
                active=active, max_len=L_max, track_quant_err=track_qerr,
                tp=tp_deg)
            from ..jit import functional_call
            (logits, _), _ = functional_call(model, state, Tensor(tokens),
                                             cache=view)
            logits = _unwrap(logits).astype(jnp.float32)    # (S, k+1, V)
            # acceptance never reaches past the cache's append capacity:
            # position j's logits are valid only while n + j < max_len
            a_cap = jnp.asarray(L_max, jnp.int32) \
                - jnp.ones((), jnp.int32) - lengths
            emitted, counts = spec_accept(logits, _unwrap(tokens), key,
                                          temps, top_ks, top_ps, k_max,
                                          max_accept=a_cap)
            # rejected drafts roll back IN-PROGRAM: lengths advance by
            # accepted+1 only; the dead tail-page rows beyond are
            # overwritten by the next step's appends
            out = view.finalize(advance=counts)
            return (emitted, counts, logits, out.k, out.v, out.k_scale,
                    out.v_scale, out.lengths, view.quant_err)

        def prefill_chunk_fn(state, tokens, slot, n_before, n_valid,
                             cache_k, cache_v, k_scale, v_scale, lengths,
                             page_table, key, temp, top_k, top_p):
            """One fixed-size chunk of one slot's prompt.  Samples a
            token from the chunk's LAST REAL position — meaningful (and
            used) only on the final chunk."""
            model.eval()
            view = PagedPrefillChunkView(
                PagedKVCache(cache_k, cache_v, page_table, lengths,
                             k_scale=k_scale, v_scale=v_scale),
                slot, n_before, n_valid, tp=tp_deg)
            from ..jit import functional_call
            (logits, _), _ = functional_call(model, state, Tensor(tokens),
                                             cache=view)
            last = jax.lax.dynamic_slice(
                logits, (jnp.zeros((), jnp.int32),
                         n_valid - jnp.ones((), jnp.int32),
                         jnp.zeros((), jnp.int32)),
                (1, 1, logits.shape[-1]))[:, 0, :]
            tok = sample(last, key, temp[None], top_k[None], top_p[None],
                         k_max)[0]
            out = view.finalize()
            return (tok, last[0], out.k, out.v, out.k_scale, out.v_scale,
                    out.lengths)

        def cow_copy_fn(cache_k, cache_v, k_scale, v_scale, src, dst):
            """Copy one page (all layers — scale rows included for the
            int8 pool) src -> dst: the copy-on-write that un-shares a
            prefix page before a write targets it."""
            src = jnp.asarray(src, jnp.int32)
            dst = jnp.asarray(dst, jnp.int32)
            k_page = jax.lax.dynamic_index_in_dim(cache_k, src, axis=0)
            v_page = jax.lax.dynamic_index_in_dim(cache_v, src, axis=0)
            zero = jnp.zeros((), jnp.int32)
            start = (dst, zero, zero, zero, zero)
            cache_k = jax.lax.dynamic_update_slice(cache_k, k_page, start)
            cache_v = jax.lax.dynamic_update_slice(cache_v, v_page, start)
            if quantized:
                ks_page = jax.lax.dynamic_index_in_dim(k_scale, src,
                                                       axis=0)
                vs_page = jax.lax.dynamic_index_in_dim(v_scale, src,
                                                       axis=0)
                k_scale = jax.lax.dynamic_update_slice(k_scale, ks_page,
                                                       start[:-1])
                v_scale = jax.lax.dynamic_update_slice(v_scale, vs_page,
                                                       start[:-1])
            return cache_k, cache_v, k_scale, v_scale

        def kv_export_fn(cache_k, cache_v, k_scale, v_scale, buf_k,
                         buf_v, buf_ks, buf_vs, page_ids):
            """Gather up to ``handoff_pages`` pool pages (all layers,
            scale rows included for the int8 pool) into the dense
            transfer buffer — the prefill side of a disaggregated
            handoff.  The buffer operands are DONATED: every chunk
            reuses the same storage instead of allocating a fresh
            multi-page buffer per transfer (TPU502 verifies the
            aliasing materializes).  ``page_ids`` entries past the
            valid count are padded with 0 — they gather page 0's bytes,
            which the import side's scatter drops."""
            ids = jnp.asarray(page_ids, jnp.int32)
            # plain [] gather keeps the index math i32 (the PR-1
            # embedding-gather discipline); ids are host-validated
            out_k = cache_k[ids]
            out_v = cache_v[ids]
            out_ks = out_vs = None
            if quantized:
                out_ks = k_scale[ids]
                out_vs = v_scale[ids]
            return out_k, out_v, out_ks, out_vs

        def kv_import_fn(cache_k, cache_v, k_scale, v_scale, buf_k,
                         buf_v, buf_ks, buf_vs, dst_ids):
            """Scatter a staged transfer buffer into freshly allocated
            pages of THIS pool — the decode side of a disaggregated
            handoff.  The pool operands are donated (in-place update,
            like every other entry); ``dst_ids`` pad entries carry
            ``num_pages``, an out-of-bounds id the default scatter mode
            drops (the paged_scatter discipline)."""
            ids = jnp.asarray(dst_ids, jnp.int32)
            cache_k = cache_k.at[ids].set(buf_k)
            cache_v = cache_v.at[ids].set(buf_v)
            if quantized:
                k_scale = k_scale.at[ids].set(buf_ks)
                v_scale = v_scale.at[ids].set(buf_vs)
            return cache_k, cache_v, k_scale, v_scale

        q = self._quantized
        self._decode_fn = decode_fn
        self._decode_donate_argnums = \
            ((1, 2, 5) + ((3, 4) if q else ())) if donate else ()
        self._verify_fn = verify_fn
        self._verify_donate_argnums = self._decode_donate_argnums
        self._prefill_chunk_fn = prefill_chunk_fn
        self._prefill_chunk_donate_argnums = \
            ((5, 6, 9) + ((7, 8) if q else ())) if donate else ()
        self._cow_fn = cow_copy_fn
        self._cow_donate_argnums = \
            ((0, 1) + ((2, 3) if q else ())) if donate else ()
        self._kv_export_fn = kv_export_fn
        self._kv_export_donate_argnums = \
            ((4, 5) + ((6, 7) if q else ())) if donate else ()
        self._kv_import_fn = kv_import_fn
        self._kv_import_donate_argnums = \
            ((0, 1) + ((2, 3) if q else ())) if donate else ()
        if self.mesh is not None:
            # every entry's SHARDED TWIN is the same traced fn jitted
            # with explicit in/out shardings: pool (+ scale pools)
            # head-sharded, everything that varies per step replicated.
            # Donated pool inputs and their outputs carry the SAME
            # sharding, so XLA's input→output aliasing materializes per
            # shard (TPU502 audits the lowered sharded entries).  The
            # scale slots are None-sharded when unquantized (the args
            # are None) and the quant_err output likewise when tracking
            # is off — None means "no leaves here", not replication.
            rep = self._sh()
            pool = self._sh(None, None, None, MP_AXIS, None)
            scale = self._sh(None, None, None, MP_AXIS) if q else None
            qe = rep if self._track_qerr else None
            state_sh = self._state_shardings()
            decode_in = (state_sh, pool, pool, scale, scale, rep, rep,
                         rep, rep, rep, rep, rep, rep)
            # the handoff transfer buffer shares the pool's head layout
            # (axis 3), so a tp engine's export/import moves only its
            # own head shard; on a 1-device (pinned) mesh it is simply
            # committed placement
            ho_in = (pool, pool, scale, scale, pool, pool, scale, scale,
                     rep)
            self._entry_shardings = {
                "serving.decode": (
                    decode_in,
                    (rep, rep, pool, pool, scale, scale, rep, qe)),
                "serving.spec_verify": (
                    decode_in,
                    (rep, rep, rep, pool, pool, scale, scale, rep, qe)),
                "serving.prefill_chunk": (
                    (state_sh, rep, rep, rep, rep, pool, pool, scale,
                     scale, rep, rep, rep, rep, rep, rep),
                    (rep, rep, pool, pool, scale, scale, rep)),
                "serving.cow_copy": (
                    (pool, pool, scale, scale, rep, rep),
                    (pool, pool, scale, scale)),
                "serving.kv_export": (ho_in, (pool, pool, scale, scale)),
                "serving.kv_import": (ho_in, (pool, pool, scale, scale)),
            }

        def _jit(entry, fn, donate_argnums):
            return jax.jit(fn, donate_argnums=donate_argnums,
                           **self._jit_kwargs(entry))

        from ..observability.watchdog import watch
        self._decode = watch(
            "serving.decode",
            _jit("serving.decode", decode_fn,
                 self._decode_donate_argnums),
            expected=1)
        self._verify = None
        if self.spec_k:
            # fixed draft length k => ONE static verify program, full
            # stop — all-accept and all-reject are traced-value paths
            self._verify = watch(
                "serving.spec_verify",
                _jit("serving.spec_verify", verify_fn,
                     self._verify_donate_argnums),
                expected=1)
        # ONE chunk shape => ONE program (vs log2(max_len) buckets)
        self._prefill_chunk = watch(
            "serving.prefill_chunk",
            _jit("serving.prefill_chunk", prefill_chunk_fn,
                 self._prefill_chunk_donate_argnums),
            expected=1)
        self._cow = watch(
            "serving.cow_copy",
            _jit("serving.cow_copy", cow_copy_fn,
                 self._cow_donate_argnums),
            expected=1)
        # fixed chunk shape => ONE program each for the disaggregated
        # page handoff (ISSUE 15): export on the prefill role, import on
        # the decode role — an engine that never hands off never
        # compiles them (the jit objects are free)
        self._kv_export = watch(
            "serving.kv_export",
            _jit("serving.kv_export", kv_export_fn,
                 self._kv_export_donate_argnums),
            expected=1)
        self._kv_import = watch(
            "serving.kv_import",
            _jit("serving.kv_import", kv_import_fn,
                 self._kv_import_donate_argnums),
            expected=1)

    # -- host-side API -----------------------------------------------------

    def refresh_state(self, state=None):
        """Re-snapshot the model's parameters (same shapes/dtypes — no
        recompile).  Call after training between generate rounds.  When
        any parameter actually CHANGED, paged engines also drop the
        prefix cache: its pages hold K/V computed under the old
        parameters, and a hash hit would silently splice stale cache
        into a fresh prompt.  Unchanged re-snapshots (every cached-
        engine reuse via ``engine_for``) keep the cache — jax arrays are
        immutable, so leaf identity is an exact change test."""
        new = state if state is not None else \
            self.model.functional_state()
        # change test against the UNSHARDED source leaves (identity —
        # jax arrays are immutable): tp engines hold device_put COPIES
        # in self.state, so comparing against those would read every
        # unchanged re-snapshot as a change — dropping the prefix cache
        # and re-uploading the whole tree per cached-engine reuse
        old_leaves = self._state_src_leaves
        new_leaves = jax.tree_util.tree_leaves(new)
        changed = (len(old_leaves) != len(new_leaves)
                   or any(a is not b
                          for a, b in zip(new_leaves, old_leaves)))
        if not changed:
            # every engine_for reuse lands here: keep the (possibly
            # sharded) placed state AND the prefix cache
            return
        self._state_src_leaves = new_leaves
        if self.paged:
            self._alloc.drop_prefix_cache()
            if self._host_tier is not None:
                # spilled rows were computed under the OLD parameters —
                # a host hit would splice stale cache exactly like the
                # device-hash hit the drop above prevents
                if self._kv_index is not None:
                    self._kv_index.withdraw(self._host_tier.digests())
                self._host_tier.clear()
                self._m_host_bytes.set(0)
        # tensor-parallel engines must RE-SHARD the changed snapshot:
        # post-training leaves are committed to their training
        # placement, and the sharded entries' in_shardings raise a
        # device-assignment mismatch on a foreign device set instead of
        # silently resharding (regression-tested); _shard_state is the
        # identity for tp=1
        self.state = self._shard_state(new)

    def reset(self):
        """Free every slot (paged: pages return to the pool and prefix
        hashes are purged; slot contents are overwritten lazily)."""
        self.kv_stats = {"tokens": 0, "paged_rows": 0, "flat_rows": 0}
        self.spec_stats = {"steps": 0, "proposed": 0, "accepted": 0}
        c = self.cache
        if self.paged:
            self._alloc.reset()
            self._len_host[:] = 0
            self._m_pool.set(0)
            lengths = jnp.zeros((self.num_slots,), jnp.int32)
            if self.mesh is not None:
                # keep the lengths COMMITTED-replicated like every other
                # call's (init device_puts, the sharded entries' outputs
                # are committed): jit keys on commitment, so a fresh
                # uncommitted zeros here would open a second cache entry
                # on the next prefill_chunk — a compile-once violation
                # the strict watchdog turns fatal mid-bench
                lengths = jax.device_put(lengths, self._sh())
            self.cache = PagedKVCache(
                c.k, c.v, self._alloc.device_table(), lengths,
                k_scale=c.k_scale, v_scale=c.v_scale)
        else:
            self.cache = SlottedKVCache(
                c.k, c.v, jnp.zeros((self.num_slots,), jnp.int32),
                k_scale=c.k_scale, v_scale=c.v_scale)

    def reseed(self, seed):
        """Restart the threaded key stream: after ``reseed(s)`` the next
        prefill/decode sequence reproduces a fresh engine built with
        ``seed=s`` (generate() calls this so its ``seed=`` argument means
        the same thing on a cached engine as on a new one)."""
        self._base_key = jax.random.key(int(seed))
        self._rng_step = 0

    def bucket_for(self, n):
        if self.paged:
            raise AttributeError("paged engines have no prefill buckets "
                                 "(one chunk program) — use prefill_chunk")
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(
            "prompt length %d exceeds the largest prefill bucket %d "
            "(max_len=%d)" % (n, self.buckets[-1], self.max_len))

    def _next_key(self):
        self._rng_step += 1
        return jax.random.fold_in(self._base_key, self._rng_step)

    def _set_quant_err(self, qerr):
        if qerr is not None:
            # opt-in: one device sync per step (same caveat as the
            # train.grad_norm gauge)
            self._m_qerr.set(float(np.asarray(qerr)))

    def _dispatch_span(self, phase, entry):
        """Context around one compiled-entry dispatch: a host span on the
        device's clock (``pt.engine.<phase>``,
        :func:`observability.tracing.annotation`) and, when the request
        tracer is on, the engine-lane span ``engine.<phase>`` carrying the
        watchdog's compile-count delta: a nonzero ``compiles`` attr on a
        steady-state step IS the silent-retrace bug class, visible at the
        exact call in the trace timeline."""
        return _DispatchSpan(self._tracer, phase, entry)

    # -- paged page bookkeeping (host side) --------------------------------

    def _set_length(self, slot, n):
        """Host-side length write (admission bookkeeping — off the
        per-token hot path)."""
        self._len_host[slot] = int(n)
        c = self.cache
        self.cache = PagedKVCache(
            c.k, c.v, c.page_table,
            c.lengths.at[int(slot)].set(int(n)),
            k_scale=c.k_scale, v_scale=c.v_scale)

    def free_slot(self, slot):
        """Release a retired slot's pages (refcounted) and zero its
        length.  Stale page-table entries are cleared so the decode
        program's (dropped) inactive-lane writes can never target a
        reassigned page."""
        if not self.paged:
            return
        self._alloc.free_slot(int(slot))
        self._set_length(int(slot), 0)
        self._slot_epoch[int(slot)] += 1
        self._m_pool.set(self._alloc.pages_used())

    def unshared_pages(self, slot):
        """Pages ONLY this slot maps — the scheduler's refcount-aware
        eviction score (freeing the max-unshared slot returns the most
        pages to the pool)."""
        return self._alloc.unshared_pages(int(slot)) if self.paged else 0

    def pages_free(self):
        return self._alloc.pages_free() if self.paged else 0

    def _cow_page(self, slot, idx):
        """Copy-on-write ``slot``'s page-table entry ``idx`` to a fresh
        private page (raises PagePoolExhausted when the pool is dry)."""
        new_pid = self._alloc.alloc()
        try:
            old_pid = int(self._alloc.table[int(slot), int(idx)])
            c = self.cache
            with self._dispatch_span("cow_copy", self._cow), \
                    x64_scope(False), self._trace_scope():
                k, v, ks, vs = self._cow(c.k, c.v, c.k_scale, c.v_scale,
                                         jnp.asarray(old_pid, jnp.int32),
                                         jnp.asarray(new_pid, jnp.int32))
        except Exception:
            # a torn COW dispatch must not strand the fresh page: the
            # pool outlives the failed step (the scheduler's tear paths
            # free the slot and keep serving the other slots)
            self._alloc._release(new_pid)
            raise
        self._alloc.remap(int(slot), int(idx), new_pid)
        self.cache = PagedKVCache(k, v, c.page_table, c.lengths,
                                  k_scale=ks, v_scale=vs)
        self._m_cow.inc()

    def _ensure_write_range(self, slot, start, stop):
        """Map (allocating) every page covering positions [start, stop)
        of ``slot`` and copy-on-write any shared page the range writes
        into.  Raises PagePoolExhausted if the pool is dry — the
        scheduler evicts a victim and retries."""
        P = self.page_size
        for idx in range(int(start) // P, (int(stop) - 1) // P + 1):
            if not self._alloc.mapped[slot, idx]:
                self._alloc.map(slot, idx, self._alloc.alloc())
            elif self._alloc.needs_cow(slot, idx):
                self._cow_page(slot, idx)
        self._m_pool.set(self._alloc.pages_used())

    def ensure_decode_ready(self, active, steps=1):
        """Pre-step page bookkeeping for one batched decode (or verify:
        ``steps = spec_k + 1`` append positions per slot): every active
        slot's append range must land in mapped, PRIVATE pages.
        Returns the first slot index that could not get a page (pool
        dry — evict and retry), or None when ready."""
        if not self.paged:
            return None
        steps = int(steps)
        for i, on in enumerate(active):
            if not on:
                continue
            p = int(self._len_host[i])
            if p >= self.max_len:
                continue        # scheduler retires this slot (cache_full)
            try:
                self._ensure_write_range(i, p, min(p + steps,
                                                   self.max_len))
            except PagePoolExhausted:
                return i
        return None

    # -- prefill -----------------------------------------------------------

    def prefill_begin(self, slot, token_ids, temperature=1.0, top_k=0,
                      top_p=1.0) -> PrefillTask:
        """Start admitting ``token_ids`` into ``slot``: map any
        hash-matched prefix pages (capped at n-1 tokens so the final
        token always runs through the chunk program and produces the
        first-token logits), then return the task whose chunks
        :meth:`prefill_step` advances."""
        if not self.paged:
            raise RuntimeError("chunked prefill is the paged path; "
                               "slotted engines use prefill()")
        ids = np.asarray(token_ids, np.int32).reshape(-1)
        n = int(ids.size)
        slot = int(slot)
        if n < 1:
            raise ValueError("empty prompt")
        if n > self.max_len:
            raise ValueError("prompt length %d > max_len %d"
                             % (n, self.max_len))
        if self._alloc.slot_pages(slot) or self._len_host[slot]:
            raise RuntimeError("slot %d admitted without free_slot()"
                               % slot)
        shared_pages, covered = self._alloc.lookup_prefix(ids)
        covered = min(covered, n - 1)
        # map only the pages the capped prefix actually covers (a capped
        # full hit keeps its tail page: its rows [.., n-1) stay valid
        # cache and the final chunk's write copy-on-writes it)
        P = self.page_size
        n_map = -(-covered // P) if covered else 0
        for idx in range(n_map):
            self._alloc.share(slot, idx, shared_pages[idx])
        self._set_length(slot, covered)
        self._m_pool.set(self._alloc.pages_used())
        return PrefillTask(slot=slot, ids=ids, pos=covered,
                           temperature=float(temperature),
                           top_k=int(top_k), top_p=float(top_p),
                           shared_tokens=covered, shared_pages=n_map)

    def prefill_step(self, task: PrefillTask, sync: bool = True) -> bool:
        """Run ONE chunk of an admission; returns True when the prompt
        is fully prefilled (``task.first_token``/``task.last_logits``
        are then set).  Raises PagePoolExhausted when the chunk's pages
        cannot be mapped — the scheduler evicts a victim and retries.

        ``sync=False`` leaves the final chunk's sampled token as the
        DEVICE array ``task.first_token_dev`` instead of blocking on
        ``int(tok)`` — the disaggregated scheduler polls
        ``.is_ready()`` between decode steps so a prefill-engine chunk
        never stalls a decode dispatch (the role-isolation contract);
        the colocated path keeps the synchronous default."""
        if task.done:
            return True
        n = int(task.ids.size)
        n_valid = min(self.prefill_chunk, n - task.pos)
        self._ensure_write_range(task.slot, task.pos, task.pos + n_valid)
        padded = np.zeros((1, self.prefill_chunk), np.int32)
        padded[0, :n_valid] = task.ids[task.pos:task.pos + n_valid]
        # only the FINAL chunk's sample is used, so only it may consume
        # a key from the threaded stream: the chunk COUNT depends on
        # prefix-cache state (a hit collapses the admission to one
        # 1-token chunk), and a per-chunk draw would shift every later
        # sample's key — generate(seed=s) must reproduce on a cached
        # engine (tested).  Non-final chunks get the never-used step-0
        # fold (_rng_step starts at 1, so it collides with nothing).
        final = task.pos + n_valid >= n
        key = (self._next_key() if final
               else jax.random.fold_in(self._base_key, 0))
        # x64_scope(False) covers the (first-call) TRACE: the serving
        # programs carry no s64/f64 — jax.random's counters and gather
        # index widening follow the global x64 default otherwise (same
        # discipline as the Pallas kernel entries; asserted over the
        # compiled HLO by tests/test_serving.py)
        with self._dispatch_span("prefill_chunk", self._prefill_chunk), \
                x64_scope(False), _eval_scope(self.model), \
                self._trace_scope():
            tok, logits, k, v, ks, vs, lengths = self._prefill_chunk(
                self.state, jnp.asarray(padded),
                jnp.asarray(task.slot, jnp.int32),
                jnp.asarray(task.pos, jnp.int32),
                jnp.asarray(n_valid, jnp.int32),
                self.cache.k, self.cache.v, *self._cache_scale_args(),
                self.cache.lengths, self._alloc.device_table(), key,
                jnp.asarray(task.temperature, jnp.float32),
                jnp.asarray(min(task.top_k, self.top_k_max), jnp.int32),
                jnp.asarray(task.top_p, jnp.float32))
        self.cache = PagedKVCache(k, v, self._alloc.device_table(),
                                  lengths, k_scale=ks, v_scale=vs)
        task.pos += n_valid
        task.chunks_run += 1
        self._len_host[task.slot] = task.pos
        if task.pos >= n:
            task.done = True
            if sync:
                task.first_token = int(tok)
            else:
                task.first_token_dev = tok
            task.last_logits = logits
            # publish this prompt's pages for later admissions to share
            servable = self._alloc.register_prefix(task.slot, task.ids)
            if self._kv_index is not None and servable:
                self._kv_index.offer(servable)
        return task.done

    def prefill(self, slot, token_ids, temperature=1.0, top_k=0,
                top_p=1.0):
        """Admit ``token_ids`` (1-D) into ``slot``; returns the sampled
        first token (int) and the last-position logits (a jax array,
        (vocab,) — left on device; np.asarray() it if needed host-side).

        Paged mode: runs every chunk back to back (the scheduler uses
        :meth:`prefill_begin`/:meth:`prefill_step` to interleave chunks
        with decode instead)."""
        if self.paged:
            task = self.prefill_begin(slot, token_ids, temperature, top_k,
                                      top_p)
            while not self.prefill_step(task):
                pass
            return task.first_token, task.last_logits
        ids = np.asarray(token_ids, np.int32).reshape(-1)
        n = int(ids.size)
        if n < 1:
            raise ValueError("empty prompt")
        if n > self.max_len:
            raise ValueError("prompt length %d > max_len %d"
                             % (n, self.max_len))
        bucket = self.bucket_for(n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = ids
        # x64/eval scopes: see prefill_step()
        with self._dispatch_span("prefill", self._prefill), \
                x64_scope(False), _eval_scope(self.model), \
                self._trace_scope():
            tok, logits, k, v, ks, vs, lengths = self._prefill(
                self.state, jnp.asarray(padded),
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(n, jnp.int32), self.cache.k, self.cache.v,
                *self._cache_scale_args(),
                self.cache.lengths, self._next_key(),
                jnp.asarray(temperature, jnp.float32),
                jnp.asarray(min(int(top_k), self.top_k_max), jnp.int32),
                jnp.asarray(top_p, jnp.float32))
        self.cache = SlottedKVCache(k, v, lengths, k_scale=ks, v_scale=vs)
        return int(tok), logits

    # -- decode ------------------------------------------------------------

    def _token_operand(self, tokens):
        """The decode entries' ``(S, 1)`` token operand.  Host arrays
        take the PR-5 path; a jax array — the previous step's sampled-
        token output, threaded back by the overlapped scheduler loop
        without a host round-trip — is reshaped eagerly.  Single-device
        jit outputs are UNCOMMITTED (re-checked on jax 0.9.0, where a RAW
        numpy operand would be a second cache entry — hence jnp.asarray
        below), so both spellings hit the SAME jit cache entry
        (compile-once holds across the mix — tested); tensor-parallel engines instead commit the host path
        onto the mesh so it matches the sharded outputs' placement (the
        PR-11 reset lesson: jit keys on commitment there)."""
        if isinstance(tokens, jax.Array):
            return jnp.reshape(tokens, (self.num_slots, 1))
        toks = np.asarray(tokens, np.int32).reshape(self.num_slots, 1)
        if self.mesh is not None:
            return jax.device_put(toks, self._sh())
        return jnp.asarray(toks)

    def decode_submit(self, tokens, active, temperature, top_k, top_p,
                      pages_ready=False) -> InflightDecode:
        """Dispatch one batched decode step WITHOUT fetching the sampled
        tokens: the returned :class:`InflightDecode` holds device-array
        futures only, so the call returns as soon as jax has enqueued
        the compiled program — the overlapped loop's *dispatch* half.
        ``tokens`` is a host array of last committed tokens, or a device
        ``(S,)`` int32 array threaded from the previous step's output.
        Host-visible bookkeeping that is deterministic at dispatch (the
        length mirror, the KV read accounting) happens HERE, so a
        sync ``decode()`` and a submit+fetch pair are byte-equivalent."""
        active_np = np.asarray(active, bool).reshape(self.num_slots)
        if self.paged and not pages_ready:
            blocked = self.ensure_decode_ready(active_np)
            if blocked is not None:
                raise PagePoolExhausted(
                    "no free page for slot %d's append — evict a slot "
                    "(the scheduler does this refcount-aware)" % blocked)
        # x64/eval scopes: see prefill_step() — keep the traced program
        # s64/f64-free and the caller's train/eval mode untouched
        with self._dispatch_span("decode", self._decode), \
                x64_scope(False), _eval_scope(self.model), \
                self._trace_scope():
            # both layouts share one call shape; paged inserts the page
            # table after lengths (donated argnums are identical)
            table = (self._alloc.device_table(),) if self.paged else ()
            tok, logits, k, v, ks, vs, lengths, qerr = self._decode(
                self.state, self.cache.k, self.cache.v,
                *self._cache_scale_args(), self.cache.lengths, *table,
                self._token_operand(tokens), jnp.asarray(active_np),
                self._next_key(),
                jnp.asarray(np.asarray(temperature, np.float32)),
                jnp.asarray(np.minimum(np.asarray(top_k, np.int32),
                                       self.top_k_max)),
                jnp.asarray(np.asarray(top_p, np.float32)))
        self.kv_stats["tokens"] += int(active_np.sum())
        self.kv_stats["flat_rows"] += self.num_slots * self.max_len
        if self.paged:
            self.cache = PagedKVCache(k, v, self._alloc.device_table(),
                                      lengths, k_scale=ks, v_scale=vs)
            # mirror the program's finalize exactly: lengths advance
            # for every active lane but clamp at max_len — a direct
            # caller keeping a full lane active has its append
            # dropped in-program, so the mirror must not advance
            # past it either.  Dispatch-time: a non-spec step's
            # advance is deterministic, so the mirror stays exact
            # even while the step is still in flight.
            self._len_host[active_np] += 1
            np.minimum(self._len_host, self.max_len,
                       out=self._len_host)
            self.kv_stats["paged_rows"] += \
                self._alloc.mapped_rows_total()
        else:
            # the slotted read bound IS the flat slots*max_len sweep
            self.cache = SlottedKVCache(k, v, lengths,
                                        k_scale=ks, v_scale=vs)
        if self._track_coll:
            # per-step collective bytes over the mesh (opt-in; priced
            # once from the compiled sharded program, then a constant)
            self._m_coll.inc(self._collective_price("serving.decode"))
        return InflightDecode(kind="decode", active=active_np, tok=tok,
                              logits=logits, qerr=qerr)

    def decode_fetch(self, step: InflightDecode):
        """Consume a dispatched decode step: the one blocking host sync
        of an engine iteration.  Returns (next_tokens as an np array,
        logits as a jax device array)."""
        if step.kind != "decode":
            raise ValueError("decode_fetch() consumes decode steps; got "
                             "a %r step (use decode_spec_fetch)"
                             % step.kind)
        step.consumed = True
        toks = np.asarray(step.tok)
        self._set_quant_err(step.qerr)
        return toks, step.logits

    def decode(self, tokens, active, temperature, top_k, top_p,
               pages_ready=False):
        """One batched decode step.  All inputs are per-slot host arrays
        of length ``num_slots``; returns (next_tokens as an np array,
        logits as a jax device array) — callers ignore entries of
        inactive slots.  ``pages_ready=True`` skips the per-slot page
        bookkeeping — for callers (the scheduler) that already ran
        :meth:`ensure_decode_ready` this step to drive eviction;
        direct callers keep the default check-and-raise.

        This is the synchronous spelling: dispatch + immediate fetch.
        The overlapped scheduler loop calls the halves directly
        (:meth:`decode_submit` / :meth:`decode_fetch`) to keep one step
        in flight."""
        return self.decode_fetch(self.decode_submit(
            tokens, active, temperature, top_k, top_p,
            pages_ready=pages_ready))

    def decode_spec_submit(self, tokens, drafts, active, temperature,
                           top_k, top_p,
                           pages_ready=False) -> InflightDecode:
        """Dispatch one speculative verify step without fetching its
        results (the overlapped loop's dispatch half — see
        :meth:`decode_submit`).  Unlike a plain decode, the per-slot
        advance (``counts``) is data-dependent, so the host length
        mirror and the spec/KV accounting are deferred to
        :meth:`decode_spec_fetch` — an overlapped caller must map the
        append range conservatively (``ensure_decode_ready`` with
        ``steps`` covering the in-flight step's worst case)."""
        if not self.spec_k:
            raise RuntimeError("decode_spec needs an engine built with "
                               "spec_k > 0")
        S, k = self.num_slots, self.spec_k
        drafts_np = np.asarray(drafts, np.int32).reshape(S, k)
        active_np = np.asarray(active, bool).reshape(S)
        if not pages_ready:
            blocked = self.ensure_decode_ready(active_np, steps=k + 1)
            if blocked is not None:
                raise PagePoolExhausted(
                    "no free page for slot %d's speculative appends — "
                    "evict a slot (the scheduler does this "
                    "refcount-aware)" % blocked)
        if isinstance(tokens, jax.Array):
            # device-threaded last committed tokens (overlapped loop)
            step_toks = jnp.concatenate(
                [jnp.reshape(tokens, (S, 1)), jnp.asarray(drafts_np)],
                axis=1)
        else:
            toks = np.asarray(tokens, np.int32).reshape(S, 1)
            step_toks = np.concatenate([toks, drafts_np], axis=1)
            if self.mesh is not None:           # see _token_operand
                step_toks = jax.device_put(step_toks, self._sh())
        with self._dispatch_span("spec_verify", self._verify), \
                x64_scope(False), _eval_scope(self.model), \
                self._trace_scope():
            emitted, counts, logits, kk, v, ks, vs, lengths, qerr = \
                self._verify(
                    self.state, self.cache.k, self.cache.v,
                    *self._cache_scale_args(), self.cache.lengths,
                    self._alloc.device_table(),
                    jnp.asarray(step_toks), jnp.asarray(active_np),
                    self._next_key(),
                    jnp.asarray(np.asarray(temperature, np.float32)),
                    jnp.asarray(np.minimum(np.asarray(top_k, np.int32),
                                           self.top_k_max)),
                    jnp.asarray(np.asarray(top_p, np.float32)))
            self.cache = PagedKVCache(kk, v, self._alloc.device_table(),
                                      lengths, k_scale=ks, v_scale=vs)
        if self._track_coll:
            self._m_coll.inc(
                self._collective_price("serving.spec_verify"))
        return InflightDecode(
            kind="spec", active=active_np, emitted=emitted, counts=counts,
            logits=logits, qerr=qerr,
            # dispatch-time read accounting: one mapped-pages sweep
            # serves every token this step commits
            paged_rows=self._alloc.mapped_rows_total(),
            slot_epoch=self._slot_epoch.copy())

    def decode_spec_fetch(self, step: InflightDecode):
        """Consume a dispatched verify step: fetch ``counts`` (the one
        blocking sync — ``emitted`` rides the same transfer), advance
        the host length mirror by the in-program commit, and settle the
        spec/KV accounting.  Returns ``(emitted, counts, logits)`` as
        :meth:`decode_spec` documents.

        ``spec_stats`` meter DEVICE work: under the overlapped loop an
        overshoot verify step dispatched for a since-retired slot still
        counts here (the program really ran), while the scheduler —
        correctly — never credits its tokens to the request, so the
        per-request ``spec_proposed``/``spec_accepted`` pair can run
        below these totals (sync loop: the two agree exactly)."""
        if step.kind != "spec":
            raise ValueError("decode_spec_fetch() consumes spec steps; "
                             "got a %r step (use decode_fetch)"
                             % step.kind)
        step.consumed = True
        active_np = step.active
        k = self.spec_k
        counts_np = np.asarray(step.counts, np.int64)
        # mirror the program's rollback exactly: advance by the
        # accepted+1 commit, clamped at max_len — but ONLY for lanes
        # whose slot was not freed (and possibly readmitted) while the
        # step was in flight: the overlapped loop's overshoot step must
        # not resurrect a zeroed mirror entry (its in-program advance
        # landed in pages free_slot already reclaimed)
        adv = (active_np & (self._slot_epoch == step.slot_epoch)
               if step.slot_epoch is not None else active_np)
        self._len_host[adv] += counts_np[adv]
        np.minimum(self._len_host, self.max_len, out=self._len_host)
        n_active = int(active_np.sum())
        emitted_total = int(counts_np[active_np].sum())
        self.spec_stats["steps"] += 1
        self.spec_stats["proposed"] += k * n_active
        self.spec_stats["accepted"] += emitted_total - n_active
        # read accounting: ONE mapped-pages sweep serves every token the
        # step commits (the amortization lever).  The flat baseline is
        # what a slotted NON-spec engine would read for the same tokens:
        # one slots*max_len sweep per single-token step, n_active tokens
        # per sweep — emitted_total/n_active sweeps (same normalization
        # as the plain-decode accounting, so A/B lines compare).
        self.kv_stats["tokens"] += emitted_total
        if n_active:
            self.kv_stats["flat_rows"] += (self.num_slots * self.max_len
                                           * emitted_total) / n_active
        self.kv_stats["paged_rows"] += step.paged_rows
        self._set_quant_err(step.qerr)
        return (np.asarray(step.emitted), counts_np.astype(np.int64),
                step.logits)

    def decode_spec(self, tokens, drafts, active, temperature, top_k,
                    top_p, pages_ready=False):
        """One speculative verify step (paged engines with ``spec_k``).

        ``tokens``: (num_slots,) last committed token per slot;
        ``drafts``: (num_slots, spec_k) int32 proposals (see
        :func:`.spec.propose` — quality moves throughput, never
        correctness).  Returns ``(emitted, counts, logits)``: emitted
        (num_slots, spec_k+1) np int32 whose row ``b`` holds
        ``counts[b]`` usable tokens — the accepted drafts plus one
        sampled/corrected token; logits (slots, k+1, vocab) stays on
        device.  Each slot's cache length advanced by ``counts[b]``
        (committed context; the final emitted token is appended by the
        NEXT step, exactly like :meth:`decode`).  The synchronous
        spelling of :meth:`decode_spec_submit` + fetch."""
        return self.decode_spec_fetch(self.decode_spec_submit(
            tokens, drafts, active, temperature, top_k, top_p,
            pages_ready=pages_ready))

    # -- disaggregated prefill/decode handoff (ISSUE 15) -------------------

    def _require_paged(self, what):
        if not self.paged:
            raise RuntimeError("%s is a paged-engine operation (the "
                               "slotted layout has no page pool)" % what)

    def _handoff_buf_shapes(self):
        H = self.handoff_pages
        pool = (H, self._layers, self.page_size, self._heads,
                self._head_dim)
        return pool, pool[:-1]

    def _new_handoff_buf(self):
        """A fresh transfer buffer (k, v, k_scale, v_scale) placed like
        the pool (committed onto the engine mesh when there is one, so
        the donated aliasing has matching input placement)."""
        pool_shape, scale_shape = self._handoff_buf_shapes()
        bk = jnp.zeros(pool_shape, self.cache.k.dtype)
        bv = jnp.zeros(pool_shape, self.cache.v.dtype)
        bks = bvs = None
        if self._quantized:
            bks = jnp.zeros(scale_shape, jnp.float32)
            bvs = jnp.zeros(scale_shape, jnp.float32)
        if self.mesh is not None:
            psh = self._sh(None, None, None, MP_AXIS, None)
            ssh = self._sh(None, None, None, MP_AXIS)
            bk = jax.device_put(bk, psh)
            bv = jax.device_put(bv, psh)
            if self._quantized:
                bks = jax.device_put(bks, ssh)
                bvs = jax.device_put(bvs, ssh)
        return [bk, bv, bks, bvs]

    def export_pages(self, page_ids):
        """Gather up to ``handoff_pages`` pool pages into the engine's
        persistent (donated-in-place) transfer buffer — the prefill
        role's half of a disaggregated handoff.  Returns the
        ``(k, v, k_scale, v_scale)`` device arrays; rows past
        ``len(page_ids)`` hold pad garbage the import side drops.  The
        returned arrays ARE the persistent buffer: stage them onto the
        decode engine (``stage_handoff``) before the next export call
        donates the storage again (device execution order makes an
        already-dispatched stage safe)."""
        return self._export_pages_into("_handoff_buf", page_ids)

    def _export_pages_into(self, buf_attr, page_ids):
        """Shared export body: gather ``page_ids`` through the ONE
        compiled kv_export program into the persistent buffer named by
        ``buf_attr``.  The handoff path and the host-tier spill path use
        separate persistent buffers (same program — jit caches on
        shape/dtype/sharding, not array identity): a spill can fire from
        an allocator reclaim WHILE a handoff chunk sits staged, and
        re-donating the handoff buffer there would tear the splice."""
        self._require_paged("export_pages")
        n = len(page_ids)
        if not 0 < n <= self.handoff_pages:
            raise ValueError("export_pages moves 1..%d pages per chunk, "
                             "got %d" % (self.handoff_pages, n))
        ids = np.zeros((self.handoff_pages,), np.int32)
        ids[:n] = np.asarray(page_ids, np.int32)
        buf = getattr(self, buf_attr)
        if buf is None:
            buf = self._new_handoff_buf()
        with self._dispatch_span("kv_export", self._kv_export), \
                x64_scope(False), self._trace_scope():
            out = self._kv_export(self.cache.k, self.cache.v,
                                  *self._cache_scale_args(),
                                  *buf, jnp.asarray(ids))
        setattr(self, buf_attr, list(out))
        return tuple(out)

    def stage_handoff(self, bufs):
        """Place a peer engine's exported transfer buffer onto THIS
        engine's devices (``jax.device_put`` — device-to-device when the
        runtime can, committed to this engine's mesh placement so the
        import's in_shardings accept it).  ``bufs`` may be device arrays
        (the direct path) or host numpy arrays (the host-staging
        fallback the scheduler uses when the meshes are disjoint).

        Meshless engines do NOT ``device_put``: their whole world is
        uncommitted (single-device jit outputs are uncommitted in this
        jax), and a committed buffer would propagate commitment through
        the import's donated pool and split the decode jit cache on the
        next step — the PR-11 reset lesson.  A meshless engine therefore
        only accepts buffers already on its (default) device; the
        scheduler validates the engine pairing at construction."""
        self._require_paged("stage_handoff")
        if self.mesh is None:
            # same-device handoff: device arrays pass through untouched,
            # host arrays (the staging fallback) lift uncommitted
            return tuple(None if a is None
                         else (a if isinstance(a, jax.Array)
                               else jnp.asarray(a))
                         for a in bufs)
        psh = self._sh(None, None, None, MP_AXIS, None)
        ssh = self._sh(None, None, None, MP_AXIS)
        return tuple(None if a is None else jax.device_put(a, t)
                     for a, t in zip(bufs, (psh, psh, ssh, ssh)))

    def import_pages(self, bufs, dst_page_ids):
        """Scatter a staged transfer buffer into THIS pool at
        ``dst_page_ids`` (freshly allocated page ids — the decode role's
        half of a handoff; the caller owns the allocator bookkeeping
        that mapped them).  Pool buffers are donated: the in-flight
        decode step's outputs are consumed in place and the next
        dispatch sees the imported pages — no host sync."""
        self._require_paged("import_pages")
        n = len(dst_page_ids)
        if not 0 < n <= self.handoff_pages:
            raise ValueError("import_pages lands 1..%d pages per chunk, "
                             "got %d" % (self.handoff_pages, n))
        # pad with num_pages: an out-of-bounds id the scatter DROPS
        ids = np.full((self.handoff_pages,), self.num_pages, np.int32)
        ids[:n] = np.asarray(dst_page_ids, np.int32)
        c = self.cache
        with self._dispatch_span("kv_import", self._kv_import), \
                x64_scope(False), self._trace_scope():
            k, v, ks, vs = self._kv_import(
                c.k, c.v, *self._cache_scale_args(), *bufs,
                jnp.asarray(ids))
        self.cache = PagedKVCache(k, v, c.page_table, c.lengths,
                                  k_scale=ks, v_scale=vs)

    def handoff_chunk_bytes(self, n_pages):
        """Bytes ``n_pages`` transferred pages move (K+V rows, scale
        rows included — ``kv_row_bytes`` truth), for the handoff
        accounting."""
        return int(n_pages) * self.page_size * self.kv_row_bytes()

    # ------------------------------------------------------------------
    # tiered KV host cache (ISSUE 17) — spill / fetch-plan / staging.
    # The scheduler owns the interleaved chunk advance (kv_tier fetch
    # machinery mirrors the disagg handoff discipline).
    # ------------------------------------------------------------------

    def _spill_page(self, pid, digests):
        """Allocator spill hook (also the explicit cold-page path):
        export one refcount-0 page's K/V rows — int8 codes + scales
        included — through the compiled kv_export program into the host
        tier under every chained digest the page is reachable by, so a
        later host hit implies exact-prefix equality.  The one blocking
        device->host copy lives here, on the rare reclaim path — never
        on a decode dispatch."""
        tier = self._host_tier
        if tier is None or not digests:
            return
        out = self._export_pages_into("_spill_buf", [pid])
        # row 0 of the spill buffer is our page; np.asarray is the
        # device->host gather (full logical heads even under tp)
        arrays = {}
        for name, a in zip(("k", "v", "ks", "vs"), out):
            if a is not None:
                arrays[name] = np.asarray(a[0])
        stored = False
        for d in digests:
            stored = tier.put(d, arrays) or stored
        if stored:
            self._m_host_spill.inc()
            self._m_host_bytes.set(tier.bytes_used())
            if self._kv_index is not None:
                self._kv_index.offer(digests)

    def spill_cached_pages(self, limit=None):
        """Explicit cold-page policy: proactively export up to ``limit``
        free-but-cached (refcount-0, hash-reachable) pages to the host
        tier and return them to the truly-free list — the long-context
        lever (cold mid-context pages spill, the hot tail stays
        resident) and the bench's device-miss/host-hit forcing lever.
        Returns the number of pages evicted from the device cache."""
        self._require_paged("spill_cached_pages")
        if self._host_tier is None:
            raise RuntimeError(
                "spill_cached_pages needs a host tier (kv_host_bytes "
                "argument or PADDLE_TPU_KV_HOST_BYTES)")
        pids = list(self._alloc._cached)
        if limit is not None:
            pids = pids[:int(limit)]
        for pid in pids:
            digests = self._alloc._page_hashes.get(pid)
            if digests:
                self._spill_page(pid, frozenset(digests))
            self._alloc.evict_cached(pid)
        return len(pids)

    def host_fetch_plan(self, ids):
        """``[(page_index, digest)]`` of contiguous host-tier pages that
        would extend the device-resident coverage of prompt ``ids`` —
        what the scheduler pulls back (chunked, interleaved between
        decode steps) before admitting the request as a full prefix hit.
        Empty when the tier is off/cold or the device cache already
        covers everything the tier could add; counts one kv_host_miss
        when the tier was consulted at the coverage boundary and had
        nothing (called once per admission attempt, so misses count
        admissions, not polls)."""
        tier = self._host_tier
        if tier is None or not self.paged:
            return []
        ids = np.asarray(ids, np.int32).reshape(-1)
        full, tail = self._alloc._prompt_digests(ids)
        entries = list(enumerate(full))
        if tail is not None:
            entries.append((len(full), tail))
        plan = []
        consulted = False
        for idx, d in entries:
            if d in self._alloc._hash_to_page:
                continue            # device-resident — nothing to fetch
            consulted = True
            if d in tier:
                plan.append((idx, d))
            else:
                break               # contiguity: stop at the first hole
        if consulted and not plan:
            self._m_host_misses.inc()
        return plan

    def host_fetch_stage(self, digests, rid=None, chunk=0):
        """Stage one fetch chunk (up to ``handoff_pages`` host-tier
        entries): read the tier arrays, assemble a transfer-buffer-shaped
        host chunk, push it through the chaos-instrumented npz staging
        roundtrip (``serve.kv_tier`` faultpoint — a torn read surfaces
        here), and place it on this engine's devices.  Returns the
        staged arrays; they are NOT donated until ``import_pages``, so
        ``is_ready()`` polling is safe.  Raises ``KeyError`` when a tier
        entry vanished (LRU raced the fetch) or a ``TRANSPORT_ERRORS``
        member on a torn staging read — the scheduler's abort path owns
        both."""
        from .kv_tier import BUF_NAMES, KV_TIER_SITE, npz_roundtrip
        self._require_paged("host_fetch_stage")
        n = len(digests)
        if not 0 < n <= self.handoff_pages:
            raise ValueError("host_fetch_stage moves 1..%d pages per "
                             "chunk, got %d" % (self.handoff_pages, n))
        tier = self._host_tier
        if tier is None:
            raise RuntimeError("host_fetch_stage needs a host tier")
        pool_shape, scale_shape = self._handoff_buf_shapes()
        bufs = {"k": np.zeros(pool_shape, np.dtype(self.cache.k.dtype)),
                "v": np.zeros(pool_shape, np.dtype(self.cache.v.dtype))}
        if self._quantized:
            bufs["ks"] = np.zeros(scale_shape, np.float32)
            bufs["vs"] = np.zeros(scale_shape, np.float32)
        for i, d in enumerate(digests):
            arrays = tier.get(d)
            if arrays is None:
                raise KeyError("host-tier entry vanished mid-fetch "
                               "(LRU eviction raced the fetch)")
            for name in bufs:
                bufs[name][i] = arrays[name]
        tup = tuple(bufs.get(name) for name in BUF_NAMES)
        tup = npz_roundtrip(tup, KV_TIER_SITE, rid=rid, chunk=chunk)
        return self.stage_handoff(tup)

    def kv_host_bytes_used(self):
        """Host-tier occupancy in bytes (0 when the tier is off) — the
        HBM ledger's host-side row."""
        tier = self._host_tier
        return 0 if tier is None else tier.bytes_used()

    def prefix_digest_snapshot(self):
        """Advisory copy of every chained page digest this engine can
        serve a prefix hit from: the device pool's hash table, the
        host tier, and anything the attached cluster index still
        offers.  The router's prefix-affinity probe (ISSUE 19) calls
        this cross-thread while the replica keeps decoding — a
        concurrent mutation just yields a marginally stale set (one
        bounded retry, then next probe refreshes), which is fine
        because affinity is a routing HINT: admission re-derives exact
        coverage under the allocator's own bookkeeping."""
        digs = set()
        if not self.paged:
            return digs
        for _ in range(4):
            try:
                digs = set(self._alloc._hash_to_page)
                tier = self._host_tier
                if tier is not None:
                    digs.update(tier.digests())
                break
            except RuntimeError:   # dict mutated under the iteration
                digs = set()
                continue
        if self._kv_index is not None:
            from .kv_tier import _hex
            digs = {_hex(d) for d in digs}
            digs.update(self._kv_index.snapshot_digests())
            return digs
        from .kv_tier import _hex
        return {_hex(d) for d in digs}

    def attach_cluster_index(self, store, host=None, interval=None,
                             start=True):
        """Wire a TCPStore-backed ClusterPrefixIndex to this engine:
        every digest that becomes servable (registered device-side or
        spilled to the host tier) is offered to the publisher, so
        replicas share one logical system-prompt cache and a router can
        read the cluster's prefix map.  Returns the index (started as a
        daemon unless ``start=False``)."""
        from .kv_tier import ClusterPrefixIndex
        self._kv_index = ClusterPrefixIndex(store, host=host,
                                            interval=interval)
        if self._host_tier is not None:
            # LRU evictions must leave the published set immediately —
            # a replica that fetches a just-evicted digest gets a miss
            # and recomputes, but a stale advertisement lingering until
            # the next interval publish turns every hit into a miss
            # storm.  withdraw() only mutates the digest set under the
            # index's own lock (store I/O stays on the publisher
            # thread), so this is safe to run from the hook, which the
            # tier invokes after releasing its lock.
            self._host_tier.evict_hook = self._kv_index.withdraw
        if start:
            self._kv_index.start()
        return self._kv_index

    def slot_lengths(self):
        """Per-slot valid lengths.  Paged mode serves the host mirror —
        no device->host sync on the scheduler's per-iteration path."""
        if self.paged:
            return self._len_host.copy()
        return np.asarray(self.cache.lengths)

    def kv_row_bytes(self):
        """Bytes one K+V row costs a decode read PER CHIP (all layers,
        this chip's ``heads / tp`` head shard).  int8: codes + the
        per-(row, head) f32 scale — the honest read bound, not just the
        code bytes.  Tensor parallelism divides the per-chip row by the
        TP degree (the ISSUE-12 acceptance ratio): every derived figure
        — ``kv_pool_bytes``, ``kv_bytes_per_token``, the HBM ledger —
        inherits per-shard truth from this one place."""
        if self._quantized:
            # 1-byte codes (int8 AND fp8/e4m3) + the f32 scale
            per_head = self._head_dim * self.kv_dtype.itemsize + 4
        else:
            per_head = self._head_dim * self._cache_dtype.itemsize
        return self._layers * (self._heads // self.tp) * per_head * 2

    def kv_pool_bytes(self):
        """Bytes the KV pool holds resident PER CHIP — the HBM ledger's
        ``hbm.kv_pool_bytes`` term.  Rows * ``kv_row_bytes()`` so the
        int8 accounting (codes + scales) and the tensor-parallel head
        split carry over: paged pools price every page whether mapped or
        free (the allocation is static), slotted pools the full
        ``slots * max_len`` buffer."""
        rows = (self.num_pages * self.page_size if self.paged
                else self.num_slots * self.max_len)
        return rows * self.kv_row_bytes()

    def kv_bytes_per_token(self):
        """Observed decode KV-read accounting PER CHIP: bytes per
        generated token under (a) the paged true-length bound and (b)
        the slotted ``slots*max_len`` bound — the bench's A/B line.  Row
        cost covers K+V across all layers (int8: codes + scales;
        tensor parallelism: this chip's head shard only, so a tp=2 line
        reads ~1/2 the tp=1 bound — the ISSUE-12 acceptance ratio).
        Slotted engines report only ``flat`` (their real read bound): a
        fabricated ``paged: 0.0`` would read as a datum in the A/B
        trajectory.  Speculative steps amortize ONE paged sweep over
        every committed token, so the paged line reflects every
        multiplicative lever at once."""
        row = self.kv_row_bytes()
        t = self.kv_stats["tokens"]
        out = {"flat": (float(self.num_slots * self.max_len * row)
                        if not t    # no decode yet: the static bound
                        else self.kv_stats["flat_rows"] * row / t)}
        if self.paged:
            out["paged"] = (0.0 if not t
                            else self.kv_stats["paged_rows"] * row / t)
        return out

    # -- flight-recorder state summary -------------------------------------

    def flight_state(self):
        """JSON-ready engine state for a flight dump: the slot table
        (per-slot lengths + mapped page ids), page-pool occupancy, and
        the watchdog compile counts.  Paged engines read only host
        state; the slotted layout's lengths live on DEVICE — and in the
        strict-recompile crash this dump exists for, the offending call
        has already consumed that donated buffer, so the read is
        guarded: a deleted-buffer error costs the lengths field, never
        the rest of the summary."""
        try:
            lengths = [int(x) for x in self.slot_lengths()]
        except Exception as e:    # donated-away device buffer mid-crash
            lengths = "unavailable: %r" % (e,)
        st = {
            "paged": self.paged,
            "num_slots": self.num_slots,
            "max_len": self.max_len,
            "kv_dtype": str(self.kv_dtype),
            "spec_k": self.spec_k,
            "tp": self.tp,
            "slot_lengths": lengths,
            "compile_counts": {
                "decode": self.decode_compile_count,
                "prefill": self.prefill_compile_count,
                "verify": self.verify_compile_count,
            },
        }
        if self.paged:
            al = self._alloc
            st["compile_counts"]["kv_export"] = \
                int(self._kv_export._cache_size())
            st["compile_counts"]["kv_import"] = \
                int(self._kv_import._cache_size())
            st.update(
                num_pages=self.num_pages,
                page_size=self.page_size,
                pages_used=al.pages_used(),
                pages_free=al.pages_free(),
                pages_cached=al.pages_cached(),
                slot_pages={
                    str(i): [int(al.table[i, j])
                             for j in np.nonzero(al.mapped[i])[0]]
                    for i in range(self.num_slots)},
            )
            if self._host_tier is not None:
                st["kv_host"] = self._host_tier.state()
        return st

    # -- compile accounting (the "compiles exactly once" contract) ---------

    @property
    def decode_compile_count(self):
        """Number of programs the decode jit holds — MUST stay 1."""
        return int(self._decode._cache_size())

    @property
    def verify_compile_count(self):
        """Programs the speculative verify jit holds — MUST stay <= 1
        (0 until the first verify call; fixed k keeps it there)."""
        if not self.spec_k:
            return 0
        return int(self._verify._cache_size())

    @property
    def prefill_compile_count(self):
        """Paged: the single chunk program; slotted: <= len(buckets)."""
        if self.paged:
            return int(self._prefill_chunk._cache_size())
        return int(self._prefill._cache_size())

    # -- audit hooks (analysis/trace/programs.py `serving` builder) --------

    def decode_trace_args(self):
        """The exact argument avals ``self._decode`` runs with (fixed key,
        not drawn from the engine stream — lowering an audit must not
        shift the live engine's sampling sequence)."""
        s = self.num_slots
        common = (jnp.zeros((s, 1), jnp.int32), jnp.ones((s,), bool),
                  jax.random.key(0), jnp.ones((s,), jnp.float32),
                  jnp.zeros((s,), jnp.int32), jnp.ones((s,), jnp.float32))
        head = (self.state, self.cache.k, self.cache.v,
                *self._cache_scale_args(), self.cache.lengths)
        if self.paged:
            return head + (self._alloc.device_table(),) + common
        return head + common

    def verify_trace_args(self):
        """Argument avals for the speculative verify entry (paged +
        spec_k engines)."""
        if not self.spec_k:
            raise RuntimeError("verify_trace_args needs spec_k > 0")
        s = self.num_slots
        return (self.state, self.cache.k, self.cache.v,
                *self._cache_scale_args(), self.cache.lengths,
                self._alloc.device_table(),
                jnp.zeros((s, self.spec_k + 1), jnp.int32),
                jnp.ones((s,), bool), jax.random.key(0),
                jnp.ones((s,), jnp.float32), jnp.zeros((s,), jnp.int32),
                jnp.ones((s,), jnp.float32))

    def prefill_trace_args(self, bucket=None):
        if self.paged:
            raise RuntimeError("paged engines trace prefill_chunk — use "
                               "prefill_chunk_trace_args()")
        b = int(bucket or self.buckets[0])
        return (self.state, jnp.zeros((1, b), jnp.int32),
                jnp.zeros((), jnp.int32), jnp.asarray(b, jnp.int32),
                self.cache.k, self.cache.v, *self._cache_scale_args(),
                self.cache.lengths, jax.random.key(0),
                jnp.ones((), jnp.float32), jnp.zeros((), jnp.int32),
                jnp.ones((), jnp.float32))

    def prefill_chunk_trace_args(self):
        C = self.prefill_chunk
        return (self.state, jnp.zeros((1, C), jnp.int32),
                jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                jnp.asarray(min(C, self.max_len), jnp.int32),
                self.cache.k, self.cache.v, *self._cache_scale_args(),
                self.cache.lengths, self._alloc.device_table(),
                jax.random.key(0), jnp.ones((), jnp.float32),
                jnp.zeros((), jnp.int32), jnp.ones((), jnp.float32))

    def cow_trace_args(self):
        return (self.cache.k, self.cache.v, *self._cache_scale_args(),
                jnp.zeros((), jnp.int32), jnp.ones((), jnp.int32))

    def kv_export_trace_args(self):
        """Argument avals for the handoff export entry (fresh zero
        buffers, NOT the live persistent one — lowering an audit must
        not race a real handoff's donated storage)."""
        self._require_paged("kv_export_trace_args")
        return (self.cache.k, self.cache.v, *self._cache_scale_args(),
                *self._new_handoff_buf(),
                jnp.zeros((self.handoff_pages,), jnp.int32))

    def kv_import_trace_args(self):
        self._require_paged("kv_import_trace_args")
        return (self.cache.k, self.cache.v, *self._cache_scale_args(),
                *self._new_handoff_buf(),
                jnp.full((self.handoff_pages,), self.num_pages,
                         jnp.int32))

    # -- cost reports (ISSUE 11) -------------------------------------------

    def cost_reports(self, only=None):
        """{watchdog entry name: ProgramReport} for every entry this
        engine watches — XLA cost/memory analysis of the programs that
        actually serve: audit trace args, production donation + x64
        scope, and NO keep_unused (unlike the audit wrap — pricing
        wants the pruned program that runs, not the alignment shim
        TPU502 needs).  Lowers + compiles each entry once per call (the jit
        dispatch cache is separate from the AOT path): cold path only —
        benches call it AFTER the timed drain.  ``only`` (an iterable of
        entry names) restricts pricing to those entries — a bench line
        that reports one program must not pay 3 extra compiles."""
        from ..observability import costs as _costs
        entries = [("serving.decode", self._decode_fn,
                    self._decode_donate_argnums, self.decode_trace_args())]
        if self.paged:
            entries.append(("serving.prefill_chunk", self._prefill_chunk_fn,
                            self._prefill_chunk_donate_argnums,
                            self.prefill_chunk_trace_args()))
            entries.append(("serving.cow_copy", self._cow_fn,
                            self._cow_donate_argnums, self.cow_trace_args()))
            entries.append(("serving.kv_export", self._kv_export_fn,
                            self._kv_export_donate_argnums,
                            self.kv_export_trace_args()))
            entries.append(("serving.kv_import", self._kv_import_fn,
                            self._kv_import_donate_argnums,
                            self.kv_import_trace_args()))
            if self.spec_k:
                entries.append(("serving.spec_verify", self._verify_fn,
                                self._verify_donate_argnums,
                                self.verify_trace_args()))
        else:
            entries.append(("serving.prefill", self._prefill_fn,
                            self._prefill_donate_argnums,
                            self.prefill_trace_args()))
        if only is not None:
            wanted = set(only)
            unknown = wanted - {name for name, *_ in entries}
            if unknown:
                raise ValueError(
                    "cost_reports(only=...) names entries this engine "
                    "does not watch: %s" % sorted(unknown))
            entries = [e for e in entries if e[0] in wanted]
        out = {}
        for name, fn, donate, args in entries:
            # tensor-parallel engines price the SHARDED twin — the
            # program that actually serves, per-chip FLOPs/bytes and
            # the partitioned collectives included (_jit_kwargs is the
            # one source of the sharding kwargs, shared with the
            # production jits)
            with x64_scope(False), self._trace_scope():
                compiled = jax.jit(fn, donate_argnums=donate,
                                   **self._jit_kwargs(name)) \
                    .lower(*args).compile()
            out[name] = _costs.report_from_compiled(name, compiled)
        return out
