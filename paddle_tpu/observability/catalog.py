"""The metric-name catalog — every metric the framework emits at runtime,
declared once (name, type, labels, unit, help).

This is the observability analogue of ops_schema.yaml: the default
registry refuses undeclared names at fetch time, and
tests/test_observability.py exercises every instrumented subsystem and
asserts the emitted set is covered here — so a dashboard never has to
chase a metric that exists only in source code, and a stale catalog entry
never outlives its instrumentation silently.

Naming: dotted ``<subsystem>.<what>_<unit>`` internally; the Prometheus
exporter rewrites dots to underscores (``serving.ttft_seconds`` ->
``serving_ttft_seconds``).  Label value spaces are bounded by
construction (finish reasons, bucket sizes, declared faultpoint sites,
watchdog entry names).
"""
from __future__ import annotations

__all__ = ["CATALOG"]


def _m(type_, help_, labels=(), unit=""):
    return {"type": type_, "help": help_, "labels": tuple(labels),
            "unit": unit}


CATALOG = {
    # -- serving (engine + continuous-batching scheduler) -------------------
    "serving.ttft_seconds": _m(
        "histogram", "submit -> first token, per finished request "
        "(INCLUDES admission-queue wait; subtract serving.queue_wait_seconds "
        "for pure prefill latency)", unit="seconds"),
    "serving.queue_wait_seconds": _m(
        "histogram", "submit -> admission (prefill start), per request",
        unit="seconds"),
    "serving.tpot_seconds": _m(
        "histogram", "mean seconds per token after the first, per finished "
        "request", unit="seconds"),
    "serving.decode_step_seconds": _m(
        "histogram", "wall time of one batched decode iteration (all slots)",
        unit="seconds"),
    "serving.generated_tokens": _m(
        "counter", "decode tokens appended to live requests (prefill "
        "first-tokens excluded)"),
    "serving.prefill_bucket_hits": _m(
        "counter", "prefill admissions per power-of-two bucket",
        labels=("bucket",)),
    "serving.finished_requests": _m(
        "counter", "retired requests by finish reason",
        labels=("reason",)),
    "serving.slot_occupancy": _m(
        "gauge", "active slots after the latest scheduler iteration"),
    "serving.queue_depth": _m(
        "gauge", "requests waiting for admission"),
    "serving.page_pool_used": _m(
        "gauge", "KV pages currently mapped by any slot (paged cache "
        "occupancy; pool size is engine.num_pages)"),
    "serving.prefix_hit_pages": _m(
        "counter", "prompt pages served from the prefix hash cache at "
        "admission instead of being recomputed/stored"),
    "serving.cow_copies": _m(
        "counter", "copy-on-write page copies (a write targeted a page "
        "shared by another slot)"),
    "serving.prefill_chunk_seconds": _m(
        "histogram", "wall time of one chunked-prefill iteration (one "
        "fixed-size chunk of one admission, interleaved with decode)",
        unit="seconds"),
    "serving.preemptions": _m(
        "counter", "requests evicted under page-pool pressure and "
        "requeued for recompute (vLLM-style preemption; a request "
        "preempted past the scheduler's cap finishes 'cache_full' "
        "instead)"),
    "serving.spec_proposed_tokens": _m(
        "counter", "draft tokens proposed to the speculative verify "
        "step (spec_k per active slot per iteration; pair with "
        "serving.spec_accepted_tokens — accept rate = accepted / "
        "proposed)"),
    "serving.spec_accepted_tokens": _m(
        "counter", "draft tokens the speculative verify step accepted "
        "(the free extra tokens per iteration; the corrective/bonus "
        "sample is not counted)"),
    "serving.kv_quant_error": _m(
        "gauge", "max abs dequantization error of the latest decode/"
        "verify step's int8 KV appends (opt-in: "
        "PADDLE_TPU_METRICS_KV_QUANT_ERROR=1 at engine construction; "
        "forces one device sync per step)"),
    "serving.tp_degree": _m(
        "gauge", "tensor-parallel degree of the most recently "
        "constructed decode engine (1 = single-chip; tp > 1 partitions "
        "the paged KV pool over heads on an ('mp',) mesh)"),
    "serving.collective_bytes": _m(
        "counter", "bytes the sharded decode/verify step's collectives "
        "move over the mesh per iteration, priced once from the "
        "compiled program's partitioned HLO (opt-in: "
        "PADDLE_TPU_METRICS_COLLECTIVES=1 at engine construction; "
        "first step pays one AOT compile for the price)"),

    # -- disaggregated prefill/decode handoff (serving/disagg.py — ISSUE 15)
    "serving.handoff_bytes": _m(
        "counter", "KV bytes moved from a prefill engine's pool into a "
        "decode engine's pool by disaggregated page handoffs (K+V rows "
        "across all layers, int8 scale rows included — kv_row_bytes "
        "truth per transferred page)", unit="bytes"),
    "serving.handoff_seconds": _m(
        "histogram", "wall time of one handoff chunk (export -> stage "
        "-> import of up to handoff_pages pages), interleaved between "
        "decode steps", unit="seconds"),
    "serving.handoff_queue_depth": _m(
        "gauge", "requests queued for or mid KV handoff (the bounded "
        "handoff queue plus in-flight transfers)"),

    # -- tiered KV host cache (serving/kv_tier.py — ISSUE 17) ---------------
    "serving.kv_host_bytes": _m(
        "gauge", "host-RAM page-tier occupancy of the most recent spill/"
        "invalidation (bounded by PADDLE_TPU_KV_HOST_BYTES; 0 = tier "
        "off or empty)", unit="bytes"),
    "serving.kv_host_hits": _m(
        "counter", "host-tier pages pulled back through kv_import and "
        "adopted device-side for an admission that missed the device "
        "prefix cache (a hit is a page that LANDED — torn fetches "
        "count nothing)"),
    "serving.kv_host_misses": _m(
        "counter", "admissions whose prompt had uncovered pages at the "
        "device-coverage boundary and the host tier held none of them "
        "(counted once per admission attempt, not per poll)"),
    "serving.kv_host_spilled_pages": _m(
        "counter", "refcount-0 hash-reachable pages exported to the "
        "host tier (allocator reclaim spills + explicit cold-page "
        "spills)"),
    "serving.kv_tier_fetch_seconds": _m(
        "histogram", "begin -> last page adopted of one host-tier "
        "fetch (interleaved between decode steps; the repeat-prompt "
        "TTFT includes this window)", unit="seconds"),

    # -- replicated serving fleet (serving/router.py — ISSUE 19) ------------
    "router.routed": _m(
        "counter", "admission routing decisions by ladder rung: "
        "affinity (prefix-digest view covered a non-empty prompt "
        "prefix), least_loaded (fresh-snapshot fallback, incl. the "
        "telemetry-blackout round-robin), failover (an orphaned "
        "in-flight request re-placed onto a survivor)",
        labels=("reason",)),
    "router.replicas_healthy": _m(
        "gauge", "replicas currently in the routable set (healthy — "
        "excludes dead, respawn-pending, and joining replicas still "
        "inside their healthy interval)"),
    "router.failovers": _m(
        "counter", "replica deaths the router failed over (crash at "
        "the serve.replica site, stalled step beacon past the "
        "deadline, or a dead thread) — each drains that replica's "
        "in-flight requests onto survivors via recompute requeue"),

    # -- serving front-end (serving/frontend.py — ISSUE 13) -----------------
    "serving.http_requests": _m(
        "counter", "HTTP requests by response status code (200 stream/"
        "complete, 400 bad request, 404, 429 shed over queue_limit, "
        "499 client disconnected mid-stream, 503 draining)",
        labels=("code",)),
    "serving.shed_total": _m(
        "counter", "requests shed by admission control (429 over the "
        "bounded queue + 503 while draining) — the load harness's shed "
        "rate numerator"),
    "serving.open_streams": _m(
        "gauge", "SSE streams currently open (connected clients being "
        "fed tokens)"),
    "serving.goodput_tokens": _m(
        "counter", "generated tokens actually DELIVERED to a connected "
        "client (streamed events that reached the socket, or the token "
        "array of a completed non-streaming response) — the goodput "
        "numerator; tokens computed for a disconnected/cancelled "
        "request never count"),

    # -- training (TrainStep / hapi fit / amp / divergence sentinel) --------
    "train.step_seconds": _m(
        "histogram", "host wall time of one TrainStep call (dispatch; on "
        "async backends completion is not awaited)", unit="seconds"),
    "train.batch_seconds": _m(
        "histogram", "hapi fit per-batch wall time incl. the loss fetch "
        "(a real device sync)", unit="seconds"),
    "train.steps": _m("counter", "TrainStep calls"),
    "train.samples": _m("counter", "leading-dim samples seen by hapi fit"),
    "train.tokens": _m(
        "counter", "batch*seq tokens seen by hapi fit (2-D+ inputs only)"),
    "train.loss": _m("gauge", "last training loss hapi fit observed"),
    "train.grad_norm": _m(
        "gauge", "global gradient norm (opt-in: "
        "PADDLE_TPU_METRICS_GRAD_NORM=1 at TrainStep construction; forces "
        "one device sync per step)"),
    "train.amp_skipped_steps": _m(
        "counter", "optimizer updates the GradScaler skipped on found_inf"),
    "train.divergence_rollbacks": _m(
        "counter", "DivergenceSentinel rewinds to a snapshot"),

    # -- robustness (retry policy, chaos faultpoints) -----------------------
    "robustness.retry_attempts": _m(
        "counter", "retries scheduled by retry_call (first attempts are "
        "not counted; exhaustion raises RetryError)", labels=("op",)),
    "robustness.faultpoint_fires": _m(
        "counter", "injected faults fired by the active FaultPlan",
        labels=("site",)),

    # -- checkpoint ---------------------------------------------------------
    "checkpoint.write_seconds": _m(
        "histogram", "full checkpoint save (serialize + shard write + "
        "manifest + publish)", unit="seconds"),
    "checkpoint.write_bytes": _m(
        "histogram", "bytes per checkpoint save (manifest-intended bytes)",
        unit="bytes"),
    "checkpoint.restore_seconds": _m(
        "histogram", "checkpoint restore (read + verify + deserialize)",
        unit="seconds"),

    # -- tensor-parallel collective-matmul overlap (distributed/mp_overlap —
    # ISSUE 20) --------------------------------------------------------------
    "mp.overlap_chunks": _m(
        "counter", "overlapped collective-matmul islands built at trace "
        "time, valued at the ring chunk count each resolved (the "
        "mp_overlap autotune family's knob; single-hop qkv re-deals "
        "count 1).  Trace-time like compile.count: a compile-once "
        "program contributes once, so a growing value under steady "
        "serving is a retrace leak"),

    # -- kernels / autotune -------------------------------------------------
    "autotune.cache_hits": _m(
        "counter", "resolve() served from pin/memo/persistent cache"),
    "autotune.cache_misses": _m(
        "counter", "resolve() fell through to timed tuning or the "
        "registered default"),
    "autotune.tune_seconds": _m(
        "histogram", "wall time of one timed candidate selection",
        unit="seconds"),
    "flash.score_elements": _m(
        "counter", "attention score elements of the causal flash calls "
        "traced so far, over all heads: which='computed' is what the "
        "forward kernel's block and sub-tile walk computes (the backward "
        "walks the same tiles), which='causal' the s*(s+1)/2 a head the "
        "mask needs; (computed - causal) / computed is the share of the "
        "kernels' work that is multiplied by zero.  Trace-time like "
        "mp.overlap_chunks: a compile-once program contributes once",
        labels=("which",)),
    "flash.bwd_calls": _m(
        "counter", "differentiated flash calls traced so far by the "
        "residency their shape chose: path='resident' (one grid cell a "
        "(batch, head group), takes O and forms delta itself), 'merged' "
        "(the (nk, nq) grid walk over a full-sequence dq scratch) or "
        "'split' (dq and dk/dv kernels).  Trace-time, one inc a backward "
        "call: a compile-once program contributes once",
        labels=("path",)),
    "flash.fwd_calls": _m(
        "counter", "flash forward calls traced so far by how q, k and v "
        "reach the kernel: operands='packed' (three block index maps onto "
        "the fused projection's (b, s, 3*h*d) output, no slice pass in "
        "front of the kernel) or 'split' (three arrays).  Trace-time, one "
        "inc a forward call: a compile-once program contributes once",
        labels=("operands",)),
    "ssm.scan_calls": _m(
        "counter", "state-space scans traced so far by implementation: "
        "path='pallas' (kernels/ssd_scan.py: a forward and a backward "
        "kernel, on a TPU for chunk, state and a group's heads in whole "
        "lane tiles) or 'chunked_jnp' (chunked contractions differentiated "
        "by JAX, kept as a checkpoint of their operands: everywhere "
        "else).  Trace-time, one inc a traced scan: a compile-once "
        "program contributes once a trace of the layer (a recomputed "
        "block is traced again)",
        labels=("path",)),
    "ssm.conv_calls": _m(
        "counter", "what stands in front of a recurrent scan (causal "
        "convolution, SiLU, the split of the fused projection, per-head "
        "L2 normalisation: nn/functional/ssm.py::conv_split_raw) traced "
        "so far by implementation: path='pallas' (kernels/causal_conv.py: "
        "a forward and a backward kernel that read the projection's buffer "
        "in place, on a TPU for an offset and parts in whole lane tiles) "
        "or 'jnp' (a slice, shifted multiply-adds and slices "
        "differentiated by JAX: everywhere else).  Trace-time, as "
        "ssm.scan_calls",
        labels=("path",)),
    "linear_attn.scan_calls": _m(
        "counter", "gated delta rules (the recurrent layer of a Gated "
        "DeltaNet mixer) traced so far by implementation: path='pallas' "
        "(kernels/delta_rule.py: a forward and a backward kernel, a chunk's "
        "(C, C) system made, inverted and used in VMEM, on a TPU for key and "
        "value heads of whole lane tiles) or 'chunked_jnp' "
        "(nn/functional/linear_attn.py: the triangular inverse inside a "
        "chunk, the state carried by a lax.scan; differentiated by JAX, "
        "kept as a checkpoint of its operands: everywhere else).  "
        "Trace-time, as ssm.scan_calls",
        labels=("path",)),
    "moe.calls": _m(
        "counter", "routed expert layers traced so far by the grouped "
        "product they launch: path='megablox' (the Pallas kernels, on a "
        "TPU) or 'ragged_dot' (elsewhere).  Trace-time, as ssm.scan_calls",
        labels=("path",)),
    "moe.rows": _m(
        "counter", "rows of the expert layers traced so far: "
        "which='routed' tokens x experts a token, 'expected_held' the "
        "share of them that uniform routing sends to the experts this "
        "chip holds, 'launched' the rows of the sorted buffer the grouped "
        "products are launched over in a step whose routing fits them "
        "(three times expected_held in whole tiles; the products visit the "
        "tiles the step's assignments cover and no others; a step that "
        "does not fit takes the dropless worst case, tokens x min(k, "
        "held), in one launch or window by window); "
        "(launched - expected_held) / launched is the padded share.  "
        "Trace-time", labels=("which",)),

    # -- compile watchdog ---------------------------------------------------
    "compile.count": _m(
        "counter", "XLA compilations per watched jit entry (the recompile "
        "watchdog warns/raises when a compile-once entry exceeds its "
        "budget)", labels=("entry",)),
    "compile.phase_seconds": _m(
        "counter", "seconds JAX spent bringing a watched entry's programs "
        "up, by phase: trace (Python to jaxpr), lower (jaxpr to "
        "StableHLO), backend (XLA's compile, or on a persistent-cache hit "
        "the read and deserialisation).  entry='(unwatched)' sums what "
        "arrived outside any watched call: eager ops, model construction, "
        "weight loading (nested traces counted again there)",
        labels=("entry", "phase"), unit="seconds"),
    "compile.cache": _m(
        "counter", "persistent compile cache verdicts (result=hit|miss), "
        "filed under the watched entry being called or under "
        "entry='(unwatched)'; silent where the cache is off",
        labels=("entry", "result")),
    "process.import_seconds": _m(
        "gauge", "wall time of `import paddle_tpu`, top of the package's "
        "__init__ to its bottom (jax's own import is outside it when the "
        "caller imported jax first)", unit="seconds"),

    # -- liveness watchdog + cluster view (observability.liveness /
    # .aggregate — armed via PADDLE_TPU_LIVENESS=1) -------------------------
    "liveness.stalls": _m(
        "counter", "stalls the liveness monitor fired: a declared "
        "progress beacon with work inflight made no progress past its "
        "deadline (each fire also produced an all-thread-stack flight "
        "dump; label space bounded by the declared beacon registry)",
        labels=("beacon",)),
    "liveness.straggler": _m(
        "gauge", "per-host straggler flag from the host-0 cluster merge "
        "(1 = this host's step-time p50 exceeds the cluster median by "
        "more than PADDLE_TPU_STRAGGLER_PCT percent, 0 = on pace; label "
        "space bounded by world size)", labels=("host",)),

    # -- HBM ledger (observability.hbm — armed via PADDLE_TPU_HBM=1) --------
    "hbm.live_bytes": _m(
        "gauge", "live device bytes per device (summed jax.live_arrays(), "
        "sampled at step/iteration boundaries by the armed ledger; a "
        "sharded array's bytes split evenly across its devices)",
        labels=("device",), unit="bytes"),
    "hbm.kv_pool_bytes": _m(
        "gauge", "summed KV-pool bytes of live serving engines (paged or "
        "slotted, int8-aware: rows * kv_row_bytes() — codes + scales)",
        unit="bytes"),
    "hbm.restore_transient_bytes": _m(
        "gauge", "host-side deserialized checkpoint tree held between "
        "read and device placement (set for the restore's duration, "
        "zero otherwise)", unit="bytes"),
}
