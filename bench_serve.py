"""Benchmark: goodput-vs-QPS through the LIVE async serving front-end.

The load harness half of ISSUE 13: starts the real HTTP front-end
(`paddle_tpu.serving.frontend`) over the compiled engine, offers Poisson
traffic at each requested QPS over a prompt/output length mix
(`paddle_tpu.serving.loadgen`), and prints ONE JSON line per (QPS, mix)
— the ``BENCH_serve_*`` trajectory format::

  {"metric": "serve_goodput_tokens_per_sec", "value": N, "unit": "tok/s",
   "qps": ..., "mix": ..., "ttft_p50_ms": ..., "ttft_p99_ms": ...,
   "tpot_p50_ms": ..., "tpot_p99_ms": ..., "shed_rate": ...,
   "cache_layout": ..., "kv_dtype": ..., "spec": ..., "tp": ...,
   "overlap": ..., "disagg": ..., "metrics": {...}, "config": {...}}

Every field the decode trajectory cursors key on rides along, plus the
serve axes (qps, mix, overlap, disagg): ``tools/bench_schema.py
--trajectory`` gates serve lines like-for-like — >3% goodput drop OR
>3% p99-TTFT growth between consecutive on-chip entries fails; CPU
lines are smoke and never perf-gate.  TTFT/TPOT here are measured at
the CLIENT (first delivered SSE token), so queueing, HTTP framing, and
the scheduler thread handoff are all inside the number — the p99 is
what a user would see, not what the engine dispatched.

**Disaggregated prefill/decode (ISSUE 15).**  ``--disagg on`` serves
through role-split engines — a prefill engine (pinned to its own device
when the backend has >= 2) hands finished KV off to the decode engine
page-chunk by page-chunk (``serving/disagg.py``); its lines carry
``"disagg": true`` plus the per-point ``handoff_bytes``/``handoffs``
and the ``serving.handoff_seconds`` histogram.  ``--disagg ab`` runs
the colocated arm then the disagg arm over the SAME seeded workload and
emits both lines.  ``--wave N`` replaces the plain load with the
interference drive (``loadgen.run_interference``): a steady stream of
``--mix`` requests plus a concurrent N-request ``prefill_heavy``
admission wave; the line's ``wave`` block reports steady-stream
inter-token p50/p99 split into quiet-vs-wave windows — the decode-TPOT
isolation headline.  ``--ab-assert`` (the CI gate) requires, with
``--disagg ab --wave N``, that the wave measurably inflates the
colocated baseline's in-flight p99 TPOT while the disagg arm inflates
strictly less.

**Replicated fleet (ISSUE 19).**  ``--replicas N`` serves through the
router tier (``serving/router.py``): N data-parallel scheduler+engine
replicas behind ONE front-end, prefix-affinity + least-loaded routing,
health-probed.  Lines carry ``"replicas": N`` (a trajectory cursor
axis, so fleet series never compare against single-replica history)
and the compile-once gate scales to N — each replica compiles each
watched program exactly once.  ``--kill-replica`` arms the chaos line:
a ``serve.replica`` HardExit kills one replica mid-drive, its in-flight
streams requeue onto survivors, and the line hard-asserts
``dropped_streams == 0`` and ``router.failovers >= 1`` — failover must
resume streams, not drop them.  The wall-clock fleet-vs-single numbers
only gate on a TPU backend (CPU replicas share host cores; same
discipline as every other arm).

The engine runs the OVERLAPPED decode loop (``--overlap off`` for the
sync A/B) under the STRICT recompile watchdog: the decode program must
compile exactly once across the whole sweep — admission churn, shed
bursts, mid-stream disconnects, handoffs, replica failovers and all
(the schema gate re-checks the reported count; disagg arms also report
``serving.kv_export``/``serving.kv_import`` at exactly 1).

On TPU: GPT-2 345M at serving shapes.  On CPU: the tiny head_dim-64
smoke config (numbers are smoke; the line carries backend so the gate
knows).  Knobs: PADDLE_TPU_BENCH_SLOTS / _REQUESTS.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time


def main(argv=None):
    os.environ.setdefault("PADDLE_TPU_STRICT_COMPILE", "1")
    ap = argparse.ArgumentParser(
        prog="python bench_serve.py",
        description="serving front-end load benchmark (goodput vs QPS)")
    ap.add_argument("--qps", default="4,16",
                    help="comma list of offered Poisson rates (one "
                         "BENCH_serve line each)")
    ap.add_argument("--mix", default="short",
                    help="prompt/output length mix name (serving."
                         "loadgen.MIXES: short|mixed|long|"
                         "prefill_heavy|decode_heavy)")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per QPS point (default 12 CPU / 32 "
                         "TPU; PADDLE_TPU_BENCH_REQUESTS overrides)")
    ap.add_argument("--queue-limit", type=int, default=32,
                    help="front-end admission bound (shed with 429 "
                         "above it)")
    ap.add_argument("--overlap", default="on", choices=("on", "off"),
                    help="overlapped host/device decode loop (off = the "
                         "sync A/B baseline)")
    ap.add_argument("--kv-dtype", default="bf16", choices=("bf16", "int8"))
    ap.add_argument("--spec", default="off",
                    help="'off' or a speculative draft length k")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (needs tp devices)")
    ap.add_argument("--disagg", default="off", choices=("off", "on", "ab"),
                    help="role-split prefill/decode serving; 'ab' runs "
                         "the colocated arm then the disagg arm over "
                         "the same seeded workload (one line each)")
    ap.add_argument("--wave", type=int, default=0, metavar="N",
                    help="interference drive: N concurrent prefill_heavy"
                         " admissions mid-stream; the line gains a "
                         "'wave' block with quiet-vs-wave in-flight "
                         "TPOT percentiles")
    ap.add_argument("--wave-repeats", type=int, default=1, metavar="K",
                    help="repeat the steady+wave cycle K times and pool "
                         "the gap samples (a one-cycle wave-window p99 "
                         "is ~the max of the set; K=3 makes the "
                         "isolation gate CI-stable)")
    ap.add_argument("--ab-assert", action="store_true",
                    help="with --disagg ab --wave N: the isolation "
                         "gate.  Always asserts STRUCTURAL isolation "
                         "(both arms measured wave-window gaps; the "
                         "disagg arm handed off and its decode engine "
                         "never compiled/ran a prefill program — "
                         "prefill compute cannot touch the decode "
                         "role).  On a TPU backend it additionally "
                         "asserts the wall-clock headline: the wave "
                         "degrades the colocated arm's in-flight p99 "
                         "TPOT (> 1.05x) and the disagg arm degrades "
                         "strictly less.  CPU hosts report the same "
                         "numbers but never perf-gate on them (CI "
                         "runners share cores across the virtual "
                         "devices, so wall-clock isolation there is "
                         "scheduling noise — the bench_schema "
                         "trajectory discipline).  Needs >= 2 devices.")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="serve through the router tier over N "
                         "data-parallel replicas (1 = classic "
                         "single-scheduler front-end)")
    ap.add_argument("--kill-replica", action="store_true",
                    help="chaos arm (needs --replicas >= 2): HardExit "
                         "one replica mid-drive at every QPS point; "
                         "hard-asserts dropped_streams == 0 and "
                         "router.failovers >= 1")
    ap.add_argument("--kill-at", type=int, default=20, metavar="K",
                    help="replica-loop iteration index (across the "
                         "fleet) at which the kill fires")
    ap.add_argument("--trace-file", default=None, metavar="PATH",
                    help="export the request-scoped span trace (JSONL) "
                         "of the LAST QPS point's drive")
    args = ap.parse_args(argv)

    import jax
    import numpy as np  # noqa: F401  (kept for parity with bench_decode)

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import flight as _flight
    from paddle_tpu.observability import tracing as _tracing
    from paddle_tpu.observability import watchdog as _wd
    from paddle_tpu.robustness import faultpoints as fp
    from paddle_tpu.serving import loadgen
    from paddle_tpu.serving.engine import DecodeEngine
    from paddle_tpu.serving.frontend import ServingFrontend
    from paddle_tpu.serving.router import Router
    from paddle_tpu.serving.scheduler import (
        ContinuousBatchingScheduler, Request)
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    spec = 0 if args.spec in ("off", "0") else int(args.spec)
    overlap = args.overlap == "on"
    on_tpu = jax.default_backend() == "tpu"
    devices = jax.devices()
    if args.tp > len(devices):
        raise SystemExit(
            "bench_serve: --tp %d needs %d devices, have %d (CPU: set "
            "XLA_FLAGS=--xla_force_host_platform_device_count)"
            % (args.tp, args.tp, len(devices)))
    if args.disagg != "off" and args.tp > 1:
        raise SystemExit("bench_serve: --disagg composes with tp on the "
                         "decode side only; run --tp separately")
    if args.ab_assert and (args.disagg != "ab" or not args.wave):
        raise SystemExit("bench_serve: --ab-assert needs --disagg ab "
                         "and --wave N")
    if args.replicas < 1:
        raise SystemExit("bench_serve: --replicas must be >= 1")
    if args.replicas > 1 and (args.disagg != "off" or args.tp > 1
                              or args.wave):
        raise SystemExit("bench_serve: --replicas composes with none of "
                         "--disagg/--tp/--wave yet — data-parallel "
                         "replicas are whole serving stacks; run those "
                         "axes per-replica in their own sweeps")
    if args.kill_replica and args.replicas < 2:
        raise SystemExit("bench_serve: --kill-replica needs "
                         "--replicas >= 2 (a failover needs a survivor)")
    if args.ab_assert and len(devices) < 2:
        raise SystemExit(
            "bench_serve: --ab-assert needs >= 2 devices so the prefill "
            "engine gets its own chip (CPU: set XLA_FLAGS="
            "--xla_force_host_platform_device_count) — on one device "
            "the roles share compute and isolation cannot show")
    paddle.seed(0)
    if on_tpu:
        cfg = GPTConfig.gpt2_medium()
        model_name = "gpt2_345m"
        num_slots, requests, max_len, page_size = 8, 32, 1024, 64
    else:
        cfg = GPTConfig(vocab_size=512, max_position_embeddings=256,
                        hidden_size=128, num_hidden_layers=2,
                        num_attention_heads=2, intermediate_size=256)
        model_name = "tiny_d64"
        num_slots, requests, max_len, page_size = 4, 12, 128, 16
    num_slots = int(os.getenv("PADDLE_TPU_BENCH_SLOTS", num_slots))
    requests = int(args.requests if args.requests is not None
                   else os.getenv("PADDLE_TPU_BENCH_REQUESTS", requests))
    cfg.hidden_dropout_prob = cfg.attention_dropout_prob = 0.0
    model = GPTForCausalLM(cfg)
    if on_tpu:
        paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    model.eval()

    qps_list = [float(t) for t in str(args.qps).split(",") if t.strip()]
    kv_dtype = "int8" if args.kv_dtype == "int8" else None

    def run_arm(disagg):
        """One sweep (all QPS points) through a fresh front-end; emits
        one schema'd line per point and returns the arm's wave block +
        isolation accounting."""
        # drop the previous arm's engines: the watchdog's
        # compile_counts() sums over LIVE same-name entries, and the
        # jitted closures hold reference cycles that outlive run_arm
        gc.collect()
        tracer = _tracing.Tracer() if args.trace_file else None
        if disagg and len(devices) >= 2:
            # role split across devices: decode on 0, prefill on 1 —
            # the whole point of the architecture (one device = smoke
            # only: roles share compute and isolation cannot show)
            decode_dev, prefill_dev = devices[0], devices[1]
        else:
            decode_dev = prefill_dev = None
        router = None
        prefill_engine = None
        if args.replicas > 1:
            # one replica a device while there are devices (a fleet that
            # leaves placement to the default is N engines on chip 0);
            # more replicas than devices share them round-robin
            engines = [DecodeEngine(model, num_slots=num_slots,
                                    max_len=max_len, seed=0,
                                    page_size=page_size,
                                    kv_dtype=kv_dtype, spec_k=spec,
                                    tracer=tracer,
                                    device=devices[i % len(devices)])
                       for i in range(args.replicas)]
            engine = engines[0]
            # deterministic per-replica warmup: routing is load-shaped,
            # so an HTTP warmup drive cannot GUARANTEE every replica
            # compiles before the measured (strict-watchdog, compile-
            # once-gated) points — drive each engine directly instead;
            # the compiled programs are engine-owned and survive into
            # the router's own schedulers
            for eng in engines:
                s = ContinuousBatchingScheduler(eng, overlap=overlap)
                s.submit(Request(
                    prompt=np.arange(1, page_size + 1, dtype=np.int32),
                    max_new_tokens=4, temperature=0.0))
                while s.has_work():
                    s.step()
            router = Router(engines, tracer=tracer, overlap=overlap,
                            respawn_delay=0.1, healthy_interval=0.5)
            fe = ServingFrontend(router=router,
                                 queue_limit=args.queue_limit,
                                 tracer=tracer)
        else:
            engine = DecodeEngine(model, num_slots=num_slots,
                                  max_len=max_len, seed=0,
                                  page_size=page_size, kv_dtype=kv_dtype,
                                  spec_k=spec, tracer=tracer, tp=args.tp,
                                  device=decode_dev)
            if disagg:
                prefill_engine = DecodeEngine(
                    model, num_slots=max(2, num_slots // 2),
                    max_len=max_len, seed=0, page_size=page_size,
                    kv_dtype=kv_dtype, tracer=tracer, device=prefill_dev)
            fe = ServingFrontend(engine, queue_limit=args.queue_limit,
                                 overlap=overlap, tracer=tracer,
                                 prefill_engine=prefill_engine)
        host, port = fe.start()
        last_wave = None

        def fleet_gap_steps():
            scheds = [r.scheduler for r in router.replicas]
            return (sum(s.host_gap_seconds for s in scheds),
                    sum(s.decode_steps_total for s in scheds))

        try:
            # warmup drive: compiles prefill + decode (+ handoff) once
            # (fleet replicas were warmed deterministically above; this
            # warms the HTTP/admission path)
            loadgen.run_load_sync(host, port, qps=max(qps_list),
                                  n_requests=2, mix=args.mix, seed=99,
                                  vocab=cfg.vocab_size)
            for qps in qps_list:
                # percentiles must describe THIS point's drive (reset
                # ordering per OBSERVABILITY.md: flight snapshot first,
                # then registry reset, then watchdog shadow resync)
                _flight.note_registry_reset()
                obs.default_registry().reset()
                _wd.resync_counter()
                if tracer is not None:
                    tracer.reset()
                sched = fe.scheduler
                if router is not None:
                    gap0, steps0 = fleet_gap_steps()
                    ho_bytes0 = ho_n0 = 0
                else:
                    gap0 = sched.host_gap_seconds
                    steps0 = sched.decode_steps_total
                    ho_bytes0 = getattr(sched, "handoff_bytes_total", 0)
                    ho_n0 = getattr(sched, "handoffs_total", 0)
                plan = None
                if args.kill_replica:
                    # the chaos plan is scoped to the MEASURED drive
                    # only (the warmup fires the same site); the kill
                    # lands a few fleet-loop iterations in, while
                    # streams are in flight
                    plan = fp.FaultPlan()
                    plan.inject("serve.replica", fp.HardExit(),
                                at=args.kill_at)
                if args.wave:
                    summary = loadgen.run_interference_sync(
                        host, port, qps=qps, n_requests=requests,
                        mix=args.mix, wave_n=args.wave, seed=0,
                        vocab=cfg.vocab_size,
                        repeats=args.wave_repeats)
                elif plan is not None:
                    with fp.chaos(plan):
                        summary = loadgen.run_load_sync(
                            host, port, qps=qps, n_requests=requests,
                            mix=args.mix, seed=0, vocab=cfg.vocab_size)
                    plan.assert_all_fired()
                else:
                    summary = loadgen.run_load_sync(
                        host, port, qps=qps, n_requests=requests,
                        mix=args.mix, seed=0, vocab=cfg.vocab_size)
                failovers = (int(obs.counter("router.failovers").value)
                             if router is not None else 0)
                if plan is not None:
                    # the killed replica must respawn and rejoin before
                    # the next point measures a degraded fleet
                    deadline = time.monotonic() + 10.0
                    while (router.healthy_count() < args.replicas
                           and time.monotonic() < deadline):
                        time.sleep(0.05)
                    if router.healthy_count() < args.replicas:
                        raise SystemExit(
                            "bench_serve: killed replica did not rejoin "
                            "within 10s (states %r)"
                            % (router.replica_states(),))

                def _pcts(name):
                    h = obs.histogram(name)
                    return {"p50_ms": round(1e3 * h.percentile(0.50), 3),
                            "p95_ms": round(1e3 * h.percentile(0.95), 3),
                            "p99_ms": round(1e3 * h.percentile(0.99), 3),
                            "count": h.count}

                hists = {
                    "serving.ttft_seconds":
                        _pcts("serving.ttft_seconds"),
                    "serving.tpot_seconds":
                        _pcts("serving.tpot_seconds"),
                    "serving.queue_wait_seconds":
                        _pcts("serving.queue_wait_seconds"),
                    "serving.decode_step_seconds":
                        _pcts("serving.decode_step_seconds"),
                }
                if disagg:
                    hists["serving.handoff_seconds"] = \
                        _pcts("serving.handoff_seconds")
                line = {
                    "metric": "serve_goodput_tokens_per_sec",
                    "value": summary["goodput_tokens_per_sec"],
                    "unit": "tok/s",
                    # the serve trajectory cursor axes (bench_schema
                    # keys series on model+layout+kv+spec+tp+overlap+
                    # disagg+qps+mix)
                    "qps": summary["qps"],
                    "mix": summary["mix"],
                    "cache_layout": "paged",
                    "kv_dtype": args.kv_dtype,
                    "spec": spec,
                    "tp": args.tp,
                    "overlap": overlap,
                    "disagg": bool(disagg),
                    "replicas": args.replicas,
                    # client-observed latency (the acceptance numbers)
                    "ttft_p50_ms": summary["ttft_p50_ms"],
                    "ttft_p99_ms": summary["ttft_p99_ms"],
                    "tpot_p50_ms": summary["tpot_p50_ms"],
                    "tpot_p99_ms": summary["tpot_p99_ms"],
                    "shed_rate": summary["shed_rate"],
                    "sent": summary["sent"],
                    "completed": summary["completed"],
                    "shed": summary["shed"],
                    "errors": summary["errors"],
                    "qps_achieved": summary["qps_achieved"],
                    "goodput_tokens": summary["goodput_tokens"],
                    "wall_s": summary["wall_s"],
                    "host_gap_ms_per_step": round(
                        1e3 * max(
                            (fleet_gap_steps()[0] if router is not None
                             else sched.host_gap_seconds) - gap0, 0.0)
                        / max((fleet_gap_steps()[1] if router is not None
                               else sched.decode_steps_total) - steps0,
                              1), 4),
                    "metrics": {
                        "histograms": hists,
                        "compile_counts": {
                            k: v for k, v in obs.compile_counts().items()
                            if v > 0},
                    },
                    "config": {
                        "model": model_name,
                        "backend": jax.default_backend(),
                        "num_slots": num_slots, "max_len": max_len,
                        "queue_limit": args.queue_limit,
                        "requests": requests, "tp": args.tp,
                        "page_size": engine.page_size,
                        "num_pages": engine.num_pages,
                        "prefill_chunk": engine.prefill_chunk,
                    },
                }
                if disagg:
                    line["handoff_bytes"] = \
                        sched.handoff_bytes_total - ho_bytes0
                    line["handoffs"] = sched.handoffs_total - ho_n0
                    line["config"]["prefill_slots"] = \
                        prefill_engine.num_slots
                    line["config"]["handoff_pages"] = \
                        engine.handoff_pages
                    line["config"]["prefill_device"] = \
                        str(prefill_dev) if prefill_dev else "shared"
                if router is not None:
                    line["dropped_streams"] = \
                        summary["dropped_streams"]
                    line["failovers"] = failovers
                    line["replicas_healthy"] = router.healthy_count()
                    line["config"]["kill_replica"] = args.kill_replica
                if args.kill_replica:
                    # the chaos line's hard gates: failover resumes
                    # streams (zero drops) and at least one failover
                    # actually happened (a vacuous kill must not pass)
                    if summary["dropped_streams"]:
                        raise SystemExit(
                            "bench_serve: %d accepted streams dropped "
                            "through the replica kill at qps=%s — "
                            "failover must resume streams, not drop "
                            "them" % (summary["dropped_streams"], qps))
                    if failovers < 1:
                        raise SystemExit(
                            "bench_serve: --kill-replica drive recorded "
                            "no router.failovers at qps=%s — the chaos "
                            "line was vacuous" % qps)
                if "wave" in summary:
                    line["wave"] = summary["wave"]
                    last_wave = summary["wave"]
                if summary["errors"]:
                    raise SystemExit(
                        "bench_serve: %d requests errored (not shed) at "
                        "qps=%s — a load line with silent failures must "
                        "not enter the trajectory" % (summary["errors"],
                                                      qps))
                if tracer is not None:
                    tracer.export_jsonl(args.trace_file)
                    counts = tracer.span_counts()
                    line["trace"] = {
                        "file": args.trace_file,
                        "spans": int(sum(counts.values())),
                        "requests": summary["completed"],
                    }
                print(json.dumps(line))
                sys.stdout.flush()
            info = {
                "wave": last_wave,
                "handoffs": getattr(sched, "handoffs_total", 0),
                "decode_route": getattr(sched,
                                        "decode_route_admissions", 0),
                "decode_chunks": getattr(sched,
                                         "decode_side_chunks", 0),
                "prefill_chunks": getattr(sched,
                                          "prefill_side_chunks", 0),
                "decode_compiles": engine.flight_state()
                                         ["compile_counts"],
                "prefill_compiles": (prefill_engine.flight_state()
                                     ["compile_counts"]
                                     if prefill_engine else None),
            }
        finally:
            fe.stop()
        return info

    arms = {"off": (False,), "on": (True,), "ab": (False, True)}
    results = {}
    for disagg in arms[args.disagg]:
        results[disagg] = run_arm(disagg)

    if args.ab_assert:
        def infl(w):
            if (not w or not w["wave_gaps"]
                    or not w["quiet_tpot_p99_ms"]):
                raise SystemExit("bench_serve: --ab-assert got no "
                                 "wave-window TPOT samples — raise "
                                 "--requests / --wave-repeats")
            if w["completed"] != w["requests"]:
                # a shed/errored wave offers no interference: a green
                # isolation verdict over it would be vacuous
                raise SystemExit(
                    "bench_serve: only %d of %d admission-wave requests "
                    "completed — raise --queue-limit or lower --wave"
                    % (w["completed"], w["requests"]))
            return w["wave_tpot_p99_ms"] / w["quiet_tpot_p99_ms"]
        colo, dis = (infl(results[False]["wave"]),
                     infl(results[True]["wave"]))
        print("# ab: colocated wave p99-TPOT inflation %.2fx, "
              "disagg %.2fx" % (colo, dis), file=sys.stderr)
        # structural isolation (every backend): the disagg arm handed
        # off, real prefill compute only ever ran on the prefill
        # engine (every decode-side chunk was a single-chunk
        # full-prefix-hit admission — no transfer, no recompute, by
        # construction 1 token), and the handoff pair compiled exactly
        # once per role
        d = results[True]
        if not d["handoffs"]:
            raise SystemExit("bench_serve: the disagg arm completed no "
                             "handoffs — the A/B never exercised the "
                             "role split")
        if not d["prefill_chunks"]:
            raise SystemExit("bench_serve: the disagg arm ran no "
                             "prefill-engine chunks")
        if d["decode_chunks"] != d["decode_route"]:
            raise SystemExit(
                "bench_serve: the disagg DECODE engine ran %d chunks "
                "for %d full-hit admissions — prefill compute leaked "
                "into the decode role"
                % (d["decode_chunks"], d["decode_route"]))
        dc, pc = d["decode_compiles"], d["prefill_compiles"]
        if dc.get("kv_import") != 1 or pc.get("kv_export") != 1:
            raise SystemExit(
                "bench_serve: handoff programs not compiled exactly "
                "once (decode %r / prefill %r)" % (dc, pc))
        # wall-clock isolation: an ON-CHIP claim (separate chips).  CPU
        # hosts share cores across the virtual devices — same
        # discipline as the trajectory gate: CPU numbers are reported,
        # never perf-gated.
        if on_tpu:
            if colo <= 1.05:
                raise SystemExit(
                    "bench_serve: the admission wave did not measurably "
                    "degrade the colocated baseline (%.2fx <= 1.05x) — "
                    "the A/B is not exercising interference; raise "
                    "--wave or prompt lengths" % colo)
            if dis >= colo:
                raise SystemExit(
                    "bench_serve: disagg in-flight p99 TPOT inflation "
                    "%.2fx is not below the colocated baseline's %.2fx "
                    "— decode-TPOT isolation regressed" % (dis, colo))


if __name__ == "__main__":
    main()
