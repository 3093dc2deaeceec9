"""Benchmark: GPT-2 345M training throughput, tokens/sec/chip, bf16.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Baseline (BASELINE.md): the reference publishes no numbers; the operative bar
is >=0.9x A100-NCCL tokens/sec/chip.  We take 60,000 tokens/s/chip as the
A100 reference point for GPT-2 345M (Megatron-style measurements at ~40% MFU
of A100's 312 bf16 TFLOP/s: 0.4*312e12 / (6*345e6 flops/token) ~= 60k) and
report vs_baseline = ours / 60000.
"""
from __future__ import annotations

import json
import time

import numpy as np

A100_TOKENS_PER_SEC = 60000.0


def main():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       GPTPretrainingCriterion)
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    on_tpu = jax.default_backend() == "tpu"
    paddle.seed(0)

    import os
    if on_tpu:
        cfg = GPTConfig.gpt2_medium()
        # 48 timed steps: a 12-step window undersold steady state by ~3%
        # on the round 1-5 set-up (dispatch ramp; see PERF.md)
        batch, seq, steps, warmup = 8, 1024, 48, 5
        batch = int(os.getenv("PADDLE_TPU_BENCH_BATCH", batch))
        seq = int(os.getenv("PADDLE_TPU_BENCH_SEQ", seq))
    else:  # CPU smoke config so bench.py always runs
        cfg = GPTConfig.tiny()
        batch, seq, steps, warmup = 2, 64, 4, 1

    cfg.hidden_dropout_prob = 0.0
    cfg.attention_dropout_prob = 0.0

    model = GPTForCausalLM(cfg)
    if on_tpu:
        paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    crit = GPTPretrainingCriterion()
    import os as _os
    # opt-in reduced-precision optimizer state A/B (PERF.md round 5)
    mdt = _os.getenv("PADDLE_TPU_BENCH_MOMENT_DTYPE") or None
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-4, weight_decay=0.01,
                                 moment_dtype=mdt)
    step = TrainStep(model, lambda logits, labels: crit(logits, labels), opt)

    ids = np.random.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    x = jnp.asarray(ids)

    # compile + warmup
    for _ in range(warmup):
        loss = step(x, x)
    loss.numpy()

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, x)
    loss._array.block_until_ready()
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    metric = ("tokens/sec/chip (GPT-2 345M bf16 train)" if on_tpu
              else "tokens/sec (GPT-2 tiny, CPU smoke)")
    result = {
        "metric": metric,
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_sec / A100_TOKENS_PER_SEC, 4),
    }
    # tie the number to the kernel configs that actually ran (autotuned,
    # cached or hand-tuned defaults — kernels/autotune.py)
    from paddle_tpu.kernels import autotune
    chosen = autotune.report()
    if chosen:
        result["autotune"] = chosen
    # telemetry block (OBSERVABILITY.md): per-step wall-time percentiles
    # from the histogram registry + compile counts from the recompile
    # watchdog — the BENCH trajectory carries percentiles from now on
    from paddle_tpu import observability as obs
    h = obs.histogram("train.step_seconds")
    result["metrics"] = {
        "histograms": {
            "train.step_seconds": {
                "p50_ms": round(1e3 * h.percentile(0.50), 3),
                "p95_ms": round(1e3 * h.percentile(0.95), 3),
                "p99_ms": round(1e3 * h.percentile(0.99), 3),
                "count": h.count,
            },
        },
        "compile_counts": obs.compile_counts(),
    }
    # cost block (ISSUE 11): XLA's own FLOPs/HBM-bytes/peak of THIS
    # compiled step, with MFU and HBM-bandwidth utilization derived from
    # the p50 step wall time when on-chip (the round-7+ headline number —
    # PERF.md).  CPU smoke lines carry null utilizations: the trajectory
    # gate validates their shape and never perf-gates them.  One extra
    # compile, strictly AFTER the timed loop.
    result["cost"] = obs.costs.cost_block(
        step.cost_report((x, x)), step_seconds=h.percentile(0.50),
        on_chip=on_tpu)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
