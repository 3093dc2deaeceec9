"""Chip probe of the gated delta rule's Pallas kernels (``paddle_tpu/
kernels/delta_rule.py``) at the shapes of ``train_qwen3next_s16384``: the
time of one layer's forward, differentiated forward and backward, and how
far kernels and ``jnp`` path stand from the float32 recurrence on the chip's
own arithmetic.  ``python tools/delta_rule_probe.py [--rehearse]``; one JSON
line a reading, the whole in ``chiprun_out/delta_rule_probe.jsonl``."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import delta_rule as K
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.nn.functional import linear_attn as FL

LINES = []


def say(**reading):
    LINES.append(reading)
    print(json.dumps(reading), flush=True)


def inputs(seed, s, hk, rep, d, dtype):
    rng = np.random.default_rng(seed)
    arr = lambda *sh: jnp.asarray(rng.normal(0, 1.0, sh), jnp.float32)
    q = FL.l2_normalize_raw(arr(1, s, hk, d), scale=d ** -0.5).astype(dtype)
    k = FL.l2_normalize_raw(arr(1, s, hk, d)).astype(dtype)
    v = arr(1, s, hk * rep, d).astype(dtype)
    g = -jnp.asarray(rng.uniform(0.001, 0.2, (1, s, hk * rep)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (1, s, hk * rep)), jnp.float32)
    return q, k, v, g, beta


def clock(fn, args, reps):
    out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps * 1e3


def times(tag, args, chunk, interpret, reps):
    for builder in (K._forward, K._backward):
        builder.clear_cache()
    rule = lambda *a: K.delta_rule(*a, chunk, interpret)
    loss = lambda *a: jnp.sum(rule(*a).astype(jnp.float32))
    fwd = clock(jax.jit(rule), args, reps)
    both = clock(jax.jit(jax.grad(loss, argnums=range(5))), args, reps)
    say(reading="time_ms", variant=tag, forward=fwd, forward_and_backward=both)


def accuracy(s, hk, rep, d, chunk, interpret, dtype):
    args = inputs(3, s, hk, rep, d, dtype)
    exact = tuple(a.astype(jnp.float32) for a in args)
    probe = jnp.asarray(np.random.default_rng(9).normal(
        0, 1, args[2].shape), jnp.float32)
    rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                             / jnp.linalg.norm(b))

    def both(fn, a):
        loss = lambda *t: jnp.sum(fn(*t).astype(jnp.float32) * probe)
        return jax.jit(fn)(*a), jax.jit(jax.grad(loss, argnums=range(5)))(*a)

    want = both(FL.gated_delta_rule_recurrence_raw, exact)
    got = {"kernel": both(lambda *a: K.delta_rule(*a, chunk, interpret), args),
           "jnp": both(lambda *a: FL._delta_chunks(
               a[0], a[1], a[2].reshape(1, s, hk, rep, d),
               a[3].reshape(1, s, hk, rep), a[4].reshape(1, s, hk, rep),
               chunk).reshape(a[2].shape), args)}
    for name, (o, grads) in got.items():
        say(reading="distance_from_recurrence", path=name,
            dtype=jnp.dtype(dtype).name, o=rel(o, want[0]),
            **{n: rel(gr, w) for n, gr, w in zip(
                ("dq", "dk", "dv", "dg", "dbeta"), grads, want[1])})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--blocks", default=str(K._BLOCK),
                    help="tokens a grid step takes, a comma between")
    opt = ap.parse_args()
    interpret = opt.rehearse
    if not interpret and jax.default_backend() != "tpu":
        sys.exit("no TPU: --rehearse runs the same code tiny in the "
                 "interpreter")
    s, hk, reps = (512, 2, 1) if interpret else (16384, 16, 10)
    say(reading="device", platform=jax.devices()[0].platform,
        kind=jax.devices()[0].device_kind, rehearsal=interpret)
    for dtype in (jnp.bfloat16, jnp.float32):
        accuracy(512 if interpret else 2048, 2, 2, 128, 64, interpret, dtype)
    args = inputs(1, s, hk, 2, 128, jnp.bfloat16)
    for block in opt.blocks.split(","):
        saved, K._BLOCK = K._BLOCK, int(block)
        times("block%s" % block, args, 64, interpret, reps)
        K._BLOCK = saved
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/delta_rule_probe.jsonl", "w") as f:
        f.writelines(json.dumps(line) + "\n" for line in LINES)


if __name__ == "__main__":
    main()
