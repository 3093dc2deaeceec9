"""Benchmark: serving decode throughput through the compiled engine.

Prints ONE JSON line per configuration (the BENCH_decode_* trajectory
format, next to the training one from bench.py):

  {"metric": "decode_tokens_per_sec", "value": N, "unit": "tok/s",
   "ttft_ms": ..., "tpot_ms": ..., "kv_bytes_per_token": {...},
   "cache_layout": ..., "kv_dtype": ..., "spec": ...,
   "compile_counts": {...}, ...}

Protocol: submit `requests` prompts through the continuous-batching
scheduler at `num_slots` concurrency and time the full drain.  Decode
throughput counts every generated token (first tokens, which are
prefill work, are reported separately via TTFT).  `compile_counts`
asserts the structural claim this engine exists for: the decode-side
step (plain decode, or the speculative verify) compiles EXACTLY ONCE no
matter how many tokens are generated, how slots churn, how many
admissions hit the prefix cache, how many chunked prefills interleave,
or what the draft accept rate does — enforced by the recompile watchdog
(paddle_tpu.observability.watchdog), which this bench arms in STRICT
mode so any retrace raises at the step that caused it instead of being
discovered in a summary line.  The `metrics` block carries p50/p95/p99
TTFT/TPOT/queue-wait from the histogram registry (reset after warmup so
percentiles describe the timed drain only).

A/B axes (ISSUE 7 + ISSUE 8 — the cartesian product of the three flags
below runs as one matrix, one JSON line each):

* `--paged` (default) / `--slotted` / `--both` — cache layout.  Paged
  reports `kv_bytes_per_token` {paged: mapped-rows bound, flat: the
  slotted slots*max_len bound}; a third of the workload reuses one
  shared prompt so prefix sharing/CoW stay on the timed path.
* `--kv-dtype bf16|int8|fp8` (comma list for a sweep) — int8 stores the
  KV pool as int8 codes + per-(row, head) f32 scales, HALVING the
  decode read bound at head_dim 64 ((64+4)/(2*64) = 0.53x the bf16 row
  — the acceptance line; the accounting charges the scale reads
  honestly).  fp8 (ISSUE 20) keeps the SAME 1-byte row and scale
  accounting with float8_e4m3fn codes — a dtype the MXU multiplies
  natively, trading int8's rounding grid for hardware-matmul codes.
* `--spec k|off` (comma list) — self-speculative decode: k prompt-lookup
  drafts per slot per iteration, one batched verify program.  Emits
  `accepted_tokens_per_step` (accepted drafts per verify iteration,
  summed over active slots — the extra tokens each program launch
  commits beyond the batch's baseline one-per-slot) and
  `spec_accept_rate` (accepted/proposed); the paged KV read is
  amortized over every committed token, so `kv_bytes_per_token.paged`
  drops with the accept rate — the second multiplicative lever on the
  same bandwidth wall.
* `--tp N` (comma list, ISSUE 12) — tensor-parallel sharded decode:
  the paged KV pool partitioned over heads on an ('mp',) mesh of N
  devices, one sharded program per entry.  `kv_bytes_per_token` is
  reported PER CHIP, so the tp=N line's paged bound is ~1/N of the
  tp=1 line — the acceptance ratio; the lever that ADDS hardware
  instead of squeezing one chip.  Needs N devices (CPU: set
  XLA_FLAGS=--xla_force_host_platform_device_count).  `tp` is a
  trajectory cursor field: tp=1 and tp=2 series never gate against
  each other.
* `--overlap-comm on|off` (comma list, ISSUE 20; tp>1 only) — the
  decomposed collective-matmul rings: the sharded decode program's
  monolithic all-gather/all-reduce islands become chunked
  collective-permute rings interleaved with the partial matmuls, so
  transfer hides behind compute.  When BOTH arms run one tp=2
  configuration, greedy output is asserted bit-identical (a two-term
  f32 sum commutes with GSPMD's reduction; wider meshes re-associate,
  so tp>2 pairs only report).  `overlap_comm` is a trajectory cursor
  field: the ring and monolithic series never gate against each other.
* `--kv-host on|off` (comma list, ISSUE 17) — the host-RAM KV page
  tier.  Every paged line appends a repeat-prompt phase (device prefix
  cache forced cold, the shared prompt re-admitted through one fresh
  scheduler) and emits `repeat_ttft_ms` + `host_hit_pages`: the tier-on
  arm must re-admit as a full prefix hit pulled back from host RAM
  (`host_hit_pages` > 0 — enforced), the tier-off arm recomputes.  When
  both arms run one configuration, the repeat drains' greedy output is
  asserted bit-identical.  `kv_host` is a trajectory cursor field:
  on and off series never gate against each other.

On TPU: GPT-2 345M at serving shapes (8 slots, 1024-token cache).
On CPU: a tiny head_dim-64 config (`tiny_d64`), so the bench always
runs AND the int8 scale-overhead ratio matches real head dims (numbers
are smoke only).  Knobs: PADDLE_TPU_BENCH_SLOTS / _PROMPT / _NEW /
_REQUESTS.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def run_config(paged: bool, kv_dtype: str, spec: int, tp: int = 1,
               overlap: bool = True, trace_file: str = None,
               kv_host: bool = False, overlap_comm: bool = False):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import tracing as _tracing
    from paddle_tpu.serving.engine import DecodeEngine
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)

    on_tpu = jax.default_backend() == "tpu"
    if tp > len(jax.devices()):
        # LOUD: a silent skip would hide a missing XLA_FLAGS in CI and
        # quietly drop a matrix line the schema gate expects
        raise SystemExit(
            "bench_decode: --tp %d needs %d devices, have %d (CPU: set "
            "XLA_FLAGS=--xla_force_host_platform_device_count)"
            % (tp, tp, len(jax.devices())))
    paddle.seed(0)

    if on_tpu:
        cfg = GPTConfig.gpt2_medium()
        model_name = "gpt2_345m"
        num_slots, prompt_len, max_new, requests = 8, 128, 128, 24
        max_len, page_size = 1024, 64
    else:  # CPU smoke config so bench_decode.py always runs; head_dim 64
        # so the int8 row ratio ((d+4)/(2d)) matches serving head dims
        cfg = GPTConfig(vocab_size=512, max_position_embeddings=256,
                        hidden_size=128, num_hidden_layers=2,
                        num_attention_heads=2, intermediate_size=256)
        model_name = "tiny_d64"
        num_slots, prompt_len, max_new, requests = 4, 24, 16, 8
        max_len, page_size = 128, 16
    num_slots = int(os.getenv("PADDLE_TPU_BENCH_SLOTS", num_slots))
    prompt_len = int(os.getenv("PADDLE_TPU_BENCH_PROMPT", prompt_len))
    max_new = int(os.getenv("PADDLE_TPU_BENCH_NEW", max_new))
    requests = int(os.getenv("PADDLE_TPU_BENCH_REQUESTS", requests))

    cfg.hidden_dropout_prob = 0.0
    cfg.attention_dropout_prob = 0.0
    model = GPTForCausalLM(cfg)
    if on_tpu:
        paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    model.eval()

    # --trace-file (ISSUE 9): a live tracer threaded through engine AND
    # scheduler; with a multi-config matrix the file holds the LAST
    # configuration's trace (each run_config overwrites it)
    tracer = _tracing.Tracer() if trace_file else None
    engine = DecodeEngine(model, num_slots=num_slots, max_len=max_len,
                          seed=0, paged=paged, page_size=page_size,
                          kv_dtype=(kv_dtype if kv_dtype in ("int8",
                                                             "fp8")
                                    else None),
                          spec_k=spec, tracer=tracer, tp=tp,
                          # ISSUE 20: an explicit bool pins the ring
                          # on/off regardless of PADDLE_TPU_MP_OVERLAP,
                          # so the off arm is a true monolithic baseline
                          overlap_comm=overlap_comm,
                          # tiered KV A/B (ISSUE 17): 0 pins the tier OFF
                          # regardless of PADDLE_TPU_KV_HOST_BYTES so the
                          # off arm is a true baseline
                          kv_host_bytes=(256 << 20) if kv_host else 0)
    rng = np.random.default_rng(0)
    # one shared "system prompt" a third of the requests reuse — the
    # prefix-sharing path must be ON the timed path, not a dead feature
    shared_prompt = rng.integers(0, cfg.vocab_size, (prompt_len,))

    def drive(n_requests):
        sched = ContinuousBatchingScheduler(engine, tracer=tracer,
                                            overlap=overlap)
        for i in range(n_requests):
            prompt = (shared_prompt if paged and i % 3 == 0
                      else rng.integers(0, cfg.vocab_size, (prompt_len,)))
            # request 0 outlives its admission wave by one page of
            # tokens: a later wave's shared-prompt admission then maps
            # its LIVE tail page (refcount 2) and the capped final-token
            # chunk write must copy-on-write first — keeps
            # serving.cow_copy on the benched path (same-wave sharers
            # miss each other: registration happens at prefill END, and
            # a retired sharer's cached page comes back at refcount 1)
            extra = page_size if (paged and i == 0) else 0
            sched.submit(Request(prompt=prompt,
                                 max_new_tokens=max_new + extra,
                                 temperature=0.0))
        t0 = time.perf_counter()
        results = sched.run()
        return results, time.perf_counter() - t0, sched

    # warmup drain: compiles prefill (one chunk program / one bucket) +
    # the decode-side step (decode, or the speculative verify) once
    drive(min(num_slots, requests))
    engine.reset()      # pages/slots back + kv/spec stats re-zeroed
    # percentiles must describe the TIMED drain, not the compile-heavy
    # warmup — drop warmup samples.  ORDERING (OBSERVABILITY.md): the
    # flight recorder snapshots the CUMULATIVE metrics first — reset()
    # zeroes exactly the counters (warmup compiles, faultpoint fires) a
    # post-mortem dump would want cumulative; then reset; then resync
    # the compile.count shadow of the watchdog (whose ground truth, the
    # jit cache sizes, survives the reset).
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import flight as _flight
    from paddle_tpu.observability import watchdog as _wd
    _flight.note_registry_reset()
    obs.default_registry().reset()
    _wd.resync_counter()
    if tracer is not None:
        tracer.reset()  # the exported trace describes the timed drain

    results, dt, sched = drive(requests)
    total_tokens = sum(r.tokens.size for r in results.values())
    # host-gap/step (ISSUE 13): wall time per decode step during which
    # NO step was dispatched-and-unconsumed — the only windows where the
    # device can be token-starved by host work.  The sync loop pays the
    # whole consume->dispatch host window every step; the overlapped
    # loop pays only true pipeline bubbles (main() asserts the
    # reduction when both modes run in one matrix).
    host_gap_ms = 1e3 * sched.host_gap_seconds \
        / max(sched.decode_steps_total, 1)
    ttft_ms = 1e3 * float(np.mean([r.ttft for r in results.values()]))
    tpot_ms = 1e3 * float(np.mean(
        [r.tpot for r in results.values() if r.tokens.size > 1]))
    prefix_hit_tokens = sum(r.prefix_hit_tokens for r in results.values())

    def _pcts(name):
        h = obs.histogram(name)
        return {"p50_ms": round(1e3 * h.percentile(0.50), 3),
                "p95_ms": round(1e3 * h.percentile(0.95), 3),
                "p99_ms": round(1e3 * h.percentile(0.99), 3),
                "count": h.count}

    kv = engine.kv_bytes_per_token()
    # the decode-side program this line reports (the verify program on a
    # speculative engine — the single-token decode never runs there)
    cost_entry = "serving.spec_verify" if spec else "serving.decode"
    from paddle_tpu.kernels import autotune as at
    result = {
        "metric": "decode_tokens_per_sec",
        "value": round(total_tokens / dt, 2),
        "unit": "tok/s",
        "ttft_ms": round(ttft_ms, 3),
        "tpot_ms": round(tpot_ms, 3),
        "total_tokens": total_tokens,
        "wall_s": round(dt, 3),
        "cache_layout": "paged" if paged else "slotted",
        # trajectory cursor keys (bench_schema gates like-for-like
        # series): the quantization, speculation and tensor-parallel axes
        "kv_dtype": kv_dtype,
        "spec": spec,
        "tp": tp,
        "overlap": overlap,
        "overlap_comm": "on" if overlap_comm else "off",
        "kv_host": "on" if kv_host else "off",
        "host_gap_ms_per_step": round(host_gap_ms, 4),
        # the ISSUE-7/8/12 acceptance line: decode KV bytes read per
        # generated token PER CHIP — `paged` scales with TRUE lengths
        # (mapped pages, amortized over every spec-committed token),
        # `flat` is the slotted slots*max_len bound; int8 halves the
        # per-row cost (codes + scales accounted) and tensor parallelism
        # divides the per-chip row by tp (the tp=N line reads ~1/N of
        # the tp=1 bound)
        "kv_bytes_per_token": {k: round(v, 1) for k, v in kv.items()},
        "prefix_hit_tokens": prefix_hit_tokens,
        # compile accounting now comes from the recompile watchdog (which
        # also enforces the budget at runtime — strict mode); the engine
        # properties remain as a cross-check.  Zero-count entries (the
        # single-token decode in a pure-spec drain) are omitted: a
        # reported entry must have compiled (schema contract).
        "compile_counts": {k: v for k, v in {
            "decode": engine.decode_compile_count,
            "verify": engine.verify_compile_count,
            "prefill": engine.prefill_compile_count,
        }.items() if v > 0},
        "metrics": {
            "histograms": {
                "serving.ttft_seconds": _pcts("serving.ttft_seconds"),
                "serving.tpot_seconds": _pcts("serving.tpot_seconds"),
                "serving.queue_wait_seconds":
                    _pcts("serving.queue_wait_seconds"),
                "serving.decode_step_seconds":
                    _pcts("serving.decode_step_seconds"),
            },
            "compile_counts": {k: v for k, v in
                               obs.compile_counts().items() if v > 0},
        },
        # cost block (ISSUE 11): XLA cost/memory analysis of the
        # decode-side program that served the drain, utilizations
        # derived from the p50 batched-step wall time when on-chip; CPU
        # smoke carries nulls (shape-only).  only= prices just this one
        # program, AFTER the timed drain.
        "cost": obs.costs.cost_block(
            engine.cost_reports(only=(cost_entry,))[cost_entry],
            step_seconds=obs.histogram(
                "serving.decode_step_seconds").percentile(0.50),
            on_chip=on_tpu),
        "config": {
            "model": model_name,
            "backend": jax.default_backend(),
            "num_slots": num_slots, "max_len": max_len,
            "prompt_len": prompt_len, "max_new_tokens": max_new,
            "requests": requests, "tp": tp,
            **({"page_size": engine.page_size,
                "num_pages": engine.num_pages,
                "prefill_chunk": engine.prefill_chunk} if paged else {}),
        },
        "autotune": at.report(),
    }
    if spec:
        st = engine.spec_stats
        result["accepted_tokens_per_step"] = round(
            st["accepted"] / max(st["steps"], 1), 3)
        result["spec_accept_rate"] = round(
            st["accepted"] / max(st["proposed"], 1), 4)
    if tracer is not None:
        tracer.export_jsonl(trace_file)
        counts = tracer.span_counts()
        # per-request span counts, keyed by rid via the trace_id each
        # RequestResult now carries (lane 0 is the shared engine lane)
        result["trace"] = {
            "file": trace_file,
            "spans": int(sum(counts.values())),
            "engine_spans": int(counts.get(0, 0)),
            "requests": len(results),
            "per_request_spans": {
                str(r.rid): int(counts.get(r.trace_id, 0))
                for r in results.values()},
        }
    # repeat-prompt A/B (ISSUE 17): force the device prefix cache cold —
    # tier ON spills the cached pages to host RAM first, tier OFF just
    # drops them — then re-admit the shared prompt.  The tier-on line
    # must re-admit as a full prefix hit served from host RAM
    # (host_hit_pages > 0); the tier-off line recomputes.  main()
    # asserts the repeat drains' greedy output bit-identical across the
    # two arms — the tier must change WHERE the KV comes from, never
    # what gets generated.
    repeat_info = None
    if paged:
        hits0 = obs.counter("serving.kv_host_hits").value
        if kv_host:
            engine.spill_cached_pages()
        else:
            engine._alloc.drop_prefix_cache()
        rsched = ContinuousBatchingScheduler(engine, overlap=overlap)
        rsched.submit(Request(prompt=shared_prompt,
                              max_new_tokens=max_new, temperature=0.0))
        rres = rsched.run()
        rr = next(iter(rres.values()))
        hit_pages = int(obs.counter("serving.kv_host_hits").value - hits0)
        if kv_host and hit_pages <= 0:
            raise SystemExit(
                "bench_decode: --kv-host on repeat admission pulled 0 "
                "pages from the host tier — the tier is not serving")
        repeat_gap_ms = 1e3 * rsched.host_gap_seconds \
            / max(rsched.decode_steps_total, 1)
        result["repeat_ttft_ms"] = round(1e3 * float(rr.ttft), 3)
        result["host_hit_pages"] = hit_pages
        result["repeat_host_gap_ms_per_step"] = round(repeat_gap_ms, 4)
        repeat_info = {"tokens": tuple(int(t) for t in rr.tokens),
                       "ttft_ms": result["repeat_ttft_ms"],
                       "hit_pages": hit_pages}
        # the repeat drain is where the kv programs first compile (the
        # spill's kv_export, the fetch's kv_import) — refresh the
        # watchdog block built above so the schema gate can hold them
        # to their budget of exactly 1
        result["metrics"]["compile_counts"] = {
            k: v for k, v in obs.compile_counts().items() if v > 0}
    print(json.dumps(result))
    sys.stdout.flush()
    # cross-mode A/B hooks for main(): the sync-vs-overlapped greedy
    # bit-parity assert, the host-gap reduction check, and the kv-host
    # repeat-prompt parity check
    tokens_by_rid = tuple(tuple(int(t) for t in results[r].tokens)
                          for r in sorted(results))
    return tokens_by_rid, host_gap_ms, repeat_info


def main(argv=None):
    # the watchdog IS the compile-count gate: any recompile of a watched
    # entry (serving.decode / serving.spec_verify budget: 1) raises
    # RecompileError mid-drain
    os.environ.setdefault("PADDLE_TPU_STRICT_COMPILE", "1")
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(
        prog="python bench_decode.py",
        description="serving decode benchmark (A/B matrix over cache "
                    "layout x kv dtype x speculative k)")
    ap.add_argument("--paged", action="store_true",
                    help="page-pool engine (the default)")
    ap.add_argument("--slotted", action="store_true",
                    help="PR-5 slotted layout (the A/B baseline)")
    ap.add_argument("--both", action="store_true",
                    help="paged AND slotted lines")
    ap.add_argument("--kv-dtype", default="bf16",
                    help="comma list of bf16|int8|fp8 (bf16 = the "
                         "unquantized pool at the activation dtype; "
                         "fp8 = float8_e4m3fn codes on the int8 "
                         "codes+scales plumbing)")
    ap.add_argument("--spec", default="off",
                    help="comma list of off|<k>: speculative draft "
                         "length per iteration (paged only)")
    ap.add_argument("--tp", default="1",
                    help="comma list of tensor-parallel degrees (paged "
                         "only; tp devices required — CPU: set "
                         "XLA_FLAGS=--xla_force_host_platform_"
                         "device_count)")
    ap.add_argument("--overlap", default="on",
                    help="comma list of on|off: the overlapped host/"
                         "device decode loop vs the sync A/B baseline "
                         "(ISSUE 13).  When BOTH run for a "
                         "configuration, greedy output is asserted "
                         "bit-identical and the overlapped host-gap/"
                         "step must not exceed the sync one")
    ap.add_argument("--overlap-comm", default="off",
                    help="comma list of on|off: decomposed "
                         "collective-matmul rings in the tp-sharded "
                         "programs (ISSUE 20; tp>1 only).  When both "
                         "arms run one tp=2 configuration, greedy "
                         "output is asserted bit-identical")
    ap.add_argument("--kv-host", default="off",
                    help="comma list of on|off: the host-RAM KV page "
                         "tier (ISSUE 17; paged only).  Every paged "
                         "line runs a repeat-prompt phase (device cache "
                         "forced cold, shared prompt re-admitted) and "
                         "emits repeat_ttft_ms + host_hit_pages; when "
                         "BOTH arms run a configuration, the repeat "
                         "drains' greedy output is asserted "
                         "bit-identical — the tier changes where the KV "
                         "comes from, never what gets generated")
    ap.add_argument("--trace-file", default=None, metavar="PATH",
                    help="export a request-scoped span trace (JSONL) of "
                         "the timed drain; feed it to `python -m "
                         "paddle_tpu.observability trace-report`.  With "
                         "a multi-config matrix the file holds the last "
                         "configuration's trace")
    args = ap.parse_args(argv)

    layouts = ([True, False] if args.both
               else [False] if args.slotted else [True])
    kv_dtypes = []
    for tok in str(args.kv_dtype).split(","):
        tok = tok.strip().lower()
        if tok not in ("bf16", "int8", "fp8"):
            ap.error("--kv-dtype values must be bf16, int8 or fp8, "
                     "got %r" % tok)
        kv_dtypes.append(tok)
    specs = []
    for tok in str(args.spec).split(","):
        tok = tok.strip().lower()
        if tok in ("off", "0"):
            specs.append(0)
        elif tok.isdigit() and int(tok) > 0:
            specs.append(int(tok))
        else:
            ap.error("--spec values must be 'off' or a positive draft "
                     "length, got %r" % tok)
    tps = []
    for tok in str(args.tp).split(","):
        tok = tok.strip()
        if tok.isdigit() and int(tok) >= 1:
            tps.append(int(tok))
        else:
            ap.error("--tp values must be positive integers, got %r"
                     % tok)
    if max(tps) > 1:
        # fail BEFORE any config runs: a mid-matrix death would burn the
        # earlier configs' warm+timed drains and emit a partial series
        import jax
        if max(tps) > len(jax.devices()):
            ap.error("--tp %d needs %d devices, have %d (CPU: set "
                     "XLA_FLAGS=--xla_force_host_platform_device_count)"
                     % (max(tps), max(tps), len(jax.devices())))

    overlaps = []
    for tok in str(args.overlap).split(","):
        tok = tok.strip().lower()
        if tok not in ("on", "off"):
            ap.error("--overlap values must be on or off, got %r" % tok)
        overlaps.append(tok == "on")

    kv_hosts = []
    for tok in str(args.kv_host).split(","):
        tok = tok.strip().lower()
        if tok not in ("on", "off"):
            ap.error("--kv-host values must be on or off, got %r" % tok)
        kv_hosts.append(tok == "on")

    overlap_comms = []
    for tok in str(args.overlap_comm).split(","):
        tok = tok.strip().lower()
        if tok not in ("on", "off"):
            ap.error("--overlap-comm values must be on or off, got %r"
                     % tok)
        overlap_comms.append(tok == "on")

    configs = [(paged, kv_dtype, spec, tp, ov, kh, oc)
               for paged in layouts
               for kv_dtype in kv_dtypes
               for spec in specs
               for tp in tps
               for ov in overlaps
               for kh in kv_hosts
               for oc in overlap_comms
               # speculation, tensor parallelism and the host KV tier
               # are paged-only
               if not ((spec or tp > 1 or kh) and not paged)
               # the ring rewrites tp-sharded programs only: an
               # overlap-comm-on tp=1 line would duplicate the tp=1
               # series under a different cursor value
               if not (oc and tp == 1)]
    if not configs:
        # e.g. --slotted --spec 4: silently emitting ZERO lines would
        # make a CI pipe fail later with an opaque empty-stdin error
        ap.error("no runnable configuration: speculative decode "
                 "(--spec > 0), tensor parallelism (--tp > 1) and the "
                 "host KV tier (--kv-host on) need the paged layout; "
                 "--overlap-comm on needs --tp > 1")
    # (paged, kv, spec, tp, kv_host, oc) -> {overlap: (tokens, gap)}
    ab = {}
    # (paged, kv, spec, tp, overlap, oc) -> {kv_host: repeat_info}
    rep = {}
    # (paged, kv, spec, tp, overlap, kv_host) -> {oc: tokens}
    ring_ab = {}
    for paged, kv_dtype, spec, tp, ov, kh, oc in configs:
        # run_config resets the registry and resyncs the watchdog after
        # its own warmup drain, so no inter-config state scrub is needed
        tokens, gap, repeat = run_config(paged, kv_dtype, spec, tp=tp,
                                         overlap=ov, kv_host=kh,
                                         overlap_comm=oc,
                                         trace_file=args.trace_file)
        ab.setdefault((paged, kv_dtype, spec, tp, kh, oc), {})[ov] = \
            (tokens, gap)
        if repeat is not None:
            rep.setdefault((paged, kv_dtype, spec, tp, ov, oc),
                           {})[kh] = repeat
        ring_ab.setdefault((paged, kv_dtype, spec, tp, ov, kh),
                           {})[oc] = tokens
    # sync-vs-overlapped A/B (the ISSUE-13 acceptance): when both modes
    # ran one configuration, greedy output must be BIT-IDENTICAL and
    # the overlapped loop's host gap must not exceed the sync loop's
    # (overlap hides host work behind device compute by construction —
    # a regression here means the pipeline stalled).
    for key, modes in ab.items():
        if len(modes) < 2:
            continue
        (tok_s, gap_s), (tok_o, gap_o) = modes[False], modes[True]
        if tok_s != tok_o:
            raise SystemExit(
                "bench_decode: sync-vs-overlapped greedy output DIVERGED "
                "for config %r — the overlapped loop's reconciliation is "
                "broken" % (key,))
        if gap_o > gap_s:
            raise SystemExit(
                "bench_decode: overlapped host-gap/step (%.4f ms) "
                "EXCEEDS the sync loop's (%.4f ms) for config %r — "
                "the overlap is not overlapping" % (gap_o, gap_s, key))
        print("bench_decode: sync-vs-overlapped A/B ok for %r — greedy "
              "bit-identical, host-gap/step %.4f -> %.4f ms"
              % (key, gap_s, gap_o), file=sys.stderr)
    # kv-host on-vs-off A/B (the ISSUE-17 acceptance): when both arms
    # ran one configuration, the repeat-prompt drains' greedy output
    # must be BIT-IDENTICAL — a host-tier splice that changed a token
    # means the fetch corrupted the cache it claims to restore.
    for key, arms in rep.items():
        if len(arms) < 2:
            continue
        off, on = arms[False], arms[True]
        if off["tokens"] != on["tokens"]:
            raise SystemExit(
                "bench_decode: kv-host on-vs-off repeat-prompt greedy "
                "output DIVERGED for config %r — the host-tier fetch "
                "spliced wrong KV" % (key,))
        print("bench_decode: kv-host A/B ok for %r — repeat greedy "
              "bit-identical, repeat TTFT %.3f (recompute) vs %.3f ms "
              "(host tier, %d pages fetched)"
              % (key, off["ttft_ms"], on["ttft_ms"], on["hit_pages"]),
              file=sys.stderr)
    # ring-vs-monolithic A/B (the ISSUE-20 acceptance): when both
    # --overlap-comm arms ran one tp=2 configuration, greedy output
    # must be BIT-IDENTICAL — every partial sum has exactly two f32
    # terms, so the ring's reduction order equals GSPMD's.  Wider
    # meshes re-associate the tree reduction (a genuine float
    # difference, not a bug), so tp>2 pairs report without gating.
    for key, arms in ring_ab.items():
        if len(arms) < 2:
            continue
        tp = key[3]
        if arms[False] != arms[True]:
            if tp == 2:
                raise SystemExit(
                    "bench_decode: overlap-comm on-vs-off greedy output "
                    "DIVERGED for tp=2 config %r — the ring computed a "
                    "different matmul" % (key,))
            print("bench_decode: overlap-comm arms differ for tp=%d "
                  "config %r (reduction re-association — expected past "
                  "tp=2)" % (tp, key), file=sys.stderr)
        else:
            print("bench_decode: overlap-comm A/B ok for %r — greedy "
                  "bit-identical" % (key,), file=sys.stderr)


if __name__ == "__main__":
    main()
