"""The ``serve_open`` and ``serve_closed`` runners: the program's HTTP/SSE
front end, scheduler and paged engine in this process, driven over loopback
by the benchmark's own generator (``chip_smoke.py::phase_serve`` is the
template for starting them).

The window is ``--seconds`` long and starts when the first request is due.
Requests that are in flight when it ends run to completion (none is cut by
the benchmark), so every request sent has a TTFT and counts in the tails;
``serve_tokens_per_s`` counts the tokens delivered inside the window only.
"""
from __future__ import annotations

import asyncio
import gc
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.lib import check, harness, loadgen, seeds, stats
from benchmarks.lib import traffic as traffic_mod

DRAIN_TIMEOUT_S = 240.0
OCCUPANCY_EVERY_S = 0.05
CHECK_REQUESTS = 8
TRACE_START_SHARE, TRACE_SECONDS = 0.4, 4.0


class Stack:
    """Model, engine and front end, started; ``stop`` drains and joins."""

    def __init__(self, run, config, traffic):
        from paddle_tpu.serving.engine import DecodeEngine
        from paddle_tpu.serving.frontend import ServingFrontend
        self.model, self.cfg, self.weights = harness.build_model(
            run, config, amp=True)
        self.model.eval()
        sizes = {k: v for k, v in traffic["engine"].items() if v is not None}
        self.engine = DecodeEngine(
            self.model, seed=seeds.small_seed(run.args.seed), **sizes)
        self.frontend = ServingFrontend(
            self.engine, queue_limit=traffic["queue_limit"])
        self.host, self.port = self.frontend.start()
        run.part("server_start")

    def stop(self):
        self.frontend.stop()
        leaked = self.engine._alloc.pages_used()
        return leaked


def _drive(stack, coro_fn):
    """Run one generator coroutine to its end, sampling the scheduler's
    slot-occupancy gauge beside it.  Returns (records, occupancy samples)."""
    from paddle_tpu import observability as obs
    gauge = obs.gauge("serving.slot_occupancy")
    samples = []

    async def main():
        task = asyncio.ensure_future(coro_fn())
        return await asyncio.wait_for(
            loadgen.sample_while(
                task, lambda: samples.append((time.perf_counter(),
                                              gauge.value)),
                OCCUPANCY_EVERY_S),
            timeout=DRAIN_TIMEOUT_S)
    return asyncio.run(main()), samples


def warm_up(run, stack, traffic, vocab_limit):
    """Every program the window will use, compiled or loaded and run once,
    through the front end."""
    plan = traffic_mod.warm_plan(traffic, run.args.seed, vocab_limit)
    recs, _ = _drive(stack, lambda: loadgen.open_loop(
        stack.host, stack.port, plan, time.perf_counter()))
    bad = [r for r in recs if not loadgen.completed(r)]
    if bad:
        raise RuntimeError("warm-up request failed: status %r, reason %r"
                           % (bad[0]["status"], bad[0]["finish_reason"]))
    run.part("warmup")


def window(run, stack, traffic, vocab_limit, seconds, rate=None,
           profiler=None):
    """Offer the cell's traffic for ``seconds`` and wait for every stream.
    Returns the run record's serving part."""
    from paddle_tpu import observability as obs
    args = run.args
    if traffic["kind"] == "serve_open":
        plan = traffic_mod.open_plan(traffic, args.seed, seconds,
                                     vocab_limit, rate)
        make = lambda t0: loadgen.open_loop(stack.host, stack.port, plan, t0)
    else:
        plan = traffic_mod.ClosedPlan(traffic, args.seed, vocab_limit)
        make = lambda t0: loadgen.closed_loop(
            stack.host, stack.port, plan.clients, plan.payload, t0, seconds)
    sched = stack.frontend.scheduler
    before = {"host_gap_s": sched.host_gap_seconds,
              "decode_steps": sched.decode_steps_total,
              "compiles": obs.compile_counts()}
    trace_box = {}

    async def traced(t0):
        task = asyncio.ensure_future(make(t0))
        if profiler is not None:
            await asyncio.sleep(max(0.0, t0 + TRACE_START_SHARE * seconds
                                    - time.perf_counter()))
            profiler.start()
            await asyncio.sleep(min(TRACE_SECONDS, 0.5 * seconds))
            # off the loop's thread: writing the trace out takes seconds,
            # and the generator must keep sending and reading meanwhile
            trace_box["trace"] = await asyncio.get_running_loop(
                ).run_in_executor(None, profiler.stop_and_reduce)
        return await task

    t0 = time.perf_counter()
    recs, occupancy = _drive(stack, lambda: traced(t0))
    t_end = time.perf_counter()
    t1 = t0 + seconds
    done = [r for r in recs if loadgen.completed(r)]
    in_window = [v for t, v in occupancy if t0 <= t <= t1]
    part = {
        "kind": traffic["kind"], "window_s": seconds, "drain_s": t_end - t1,
        "attempted": len(recs), "failed": len(recs) - len(done),
        "shed": sum(r["status"] in (429, 503) for r in recs),
        "tokens_in_window": loadgen.tokens_inside(recs, t0, t1),
        "ttft_s": loadgen.ttfts(recs, t_end), "gap_s": loadgen.gaps(recs),
        "late_s": loadgen.lateness(recs),
        "in_flight_mid": sum(1 for r in recs if r["due"] <= t0 + seconds / 2
                             and (not r["arrivals"] or r["arrivals"][-1][0]
                                  > t0 + seconds / 2)),
        "in_flight_end": sum(1 for r in recs if r["due"] <= t1
                             and (not r["arrivals"]
                                  or r["arrivals"][-1][0] > t1)),
        "occupancy_mean": (sum(in_window) / len(in_window)
                           if in_window else 0.0),
        "slots": stack.engine.num_slots,
        "host_gap_s": sched.host_gap_seconds - before["host_gap_s"],
        "decode_steps": sched.decode_steps_total - before["decode_steps"],
        "compiles_before": before["compiles"],
        "compiles_after": obs.compile_counts(),
    }
    return part, recs, trace_box.get("trace")


def end_to_end(part):
    out = {"serve_tokens_per_s": part["tokens_in_window"] / part["window_s"]}
    if part["ttft_s"]:
        out["ttft_p95_ms"] = 1e3 * stats.percentile(part["ttft_s"], 0.95)
    if part["gap_s"]:
        out["gap_p95_ms"] = 1e3 * stats.percentile(part["gap_s"], 0.95)
    return out


def summary_line(part):
    """The window's detail for an earlier line of stdout: sample counts,
    medians and the latencies that judge nothing."""
    pct = lambda xs, q: (round(1e3 * stats.percentile(xs, q), 3)
                         if xs else None)
    return {
        "attempted": part["attempted"], "failed": part["failed"],
        "shed": part["shed"], "tokens_in_window": part["tokens_in_window"],
        "window_s": part["window_s"], "drain_s": round(part["drain_s"], 3),
        "ttft_samples": len(part["ttft_s"]),
        "ttft_ms": {"p50": pct(part["ttft_s"], 0.5),
                    "p95": pct(part["ttft_s"], 0.95),
                    "max": pct(part["ttft_s"], 1.0)},
        "gap_samples": len(part["gap_s"]),
        "gap_ms": {"p50": pct(part["gap_s"], 0.5),
                   "p95": pct(part["gap_s"], 0.95),
                   "p99": pct(part["gap_s"], 0.99)},
        "late_ms": {"p50": pct(part["late_s"], 0.5),
                    "p95": pct(part["late_s"], 0.95)},
        "in_flight_mid": part["in_flight_mid"],
        "in_flight_end": part["in_flight_end"],
        "occupancy_mean": round(part["occupancy_mean"], 2),
        "decode_steps": part["decode_steps"],
        "host_gap_s": round(part["host_gap_s"], 4)}


def run(run: harness.Run, config: dict, traffic: dict, devices):
    from paddle_tpu import observability as obs
    args = run.args
    vocab_limit = config["token_id_limit"]
    stack = Stack(run, config, traffic)
    try:
        warm_up(run, stack, traffic, vocab_limit)
        run.setup_done()
        part, recs, trace = window(
            run, stack, traffic, vocab_limit, args.seconds,
            profiler=harness.Profiler() if args.trace else None)
        device = harness.device_record(devices, run.record["chips"])
        registry = obs.default_registry().snapshot()
    finally:
        leaked = stack.stop()
    run.emit(phase="window", programs_in_window=run.programs_in_window(),
             pages_left_mapped=leaked, **summary_line(part))
    run.record.update(part, device=device, device_kind=device["kind"],
                      end_to_end=end_to_end(part))

    # -- the check: after the window, outside every timed interval -----------
    t_check = time.perf_counter()
    model, cfg, weights = stack.model, stack.cfg, stack.weights
    width = stack.engine.max_len
    del stack
    gc.collect()        # the page pool goes: room for float32 logits
    verdict = _check(args.seed, model, cfg, weights, recs, width)
    verdict["pages_left_mapped"] = leaked
    verdict["within"] = bool(verdict["within"] and leaked == 0)
    run.emit(phase="check", seconds=round(time.perf_counter() - t_check, 3),
             **verdict)
    run.record["correct"] = verdict["within"]
    return registry, trace


def _check(seed, model, cfg, weights, recs, width):
    """Served tokens against the reference, teacher-forced, on a seeded
    sample of completed requests; the system's eval logits against the
    reference's on the first of them."""
    ref_forward = check.reference_forward_fn(
        cfg.num_hidden_layers, cfg.num_attention_heads,
        cfg.layer_norm_epsilon)
    done = [r for r in recs if loadgen.completed(r)]
    if not done:
        return {"kind": "serve", "within": False,
                "why": "no request completed"}
    clock = harness.PartClock()
    rng = seeds.rng(seed, "check")
    picks = [done[i] for i in rng.permutation(len(done))[:CHECK_REQUESTS]]
    sample = [(r["prompt"], r["token_ids"]) for r in picks]
    deficit = check.served_deficit(ref_forward, weights, sample, width)
    clock.part("served_deficit")
    ids = list(sample[0][0]) + list(sample[0][1])
    padded = np.zeros((1, width), np.int32)
    padded[0, :len(ids)] = ids
    padded = jnp.asarray(padded)
    # over the whole padded row: the padding is input like any other, and
    # one width means one program whatever the stream's length
    errors = check.logits_errors(
        check.system_forward_fn(model)(weights, padded),
        ref_forward(weights, padded))
    clock.part("logits_compare")
    within = (deficit < check.DEFICIT_TOL and errors["finite"]
              and errors["rel_rms"] < check.LOGITS_RMS_TOL)
    return {"kind": "serve", "requests_checked": len(sample),
            "tokens_checked": sum(len(s) for _, s in sample),
            "worst_deficit": deficit, "logits": errors,
            "parts_s": clock.parts,
            "tolerance": {"deficit": check.DEFICIT_TOL,
                          "logits_rel_rms": check.LOGITS_RMS_TOL},
            "within": bool(within)}
