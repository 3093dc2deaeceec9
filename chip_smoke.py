"""Chip smoke: the quickest proof that paddle_tpu still starts on the chip.

One process, which holds the chip from start to end and starts no child that
needs it.  Drives the system's two main paths once each, through the entry
points a user calls, at the full width of GPT-2 345M (`GPTConfig.gpt2_medium`;
depth can be cut with --layers, weights are random from --seed):

* train: `amp.decorate(O2, bfloat16)` + `AdamW` + `jit.TrainStep`, batch
  8 x 1024, one compile and six steps on a fixed batch.  Checks: the flash
  kernel agrees with the O(S^2) reference on a small input; the traced step
  holds two Pallas calls a layer (attention that took the reference path is a
  failure); losses finite, near ln(vocab) at step 1 and lower at the end;
  `jit.train_step` compiled exactly once.
* serve: the trained weights in eval behind `DecodeEngine` (paged, 8 slots x
  1024, 64-token pages) + `ServingFrontend` on loopback, driven by
  `serving.loadgen` from this process: eight streamed greedy requests, prompts
  of 130-192 tokens, 32-64 new tokens, two of them the same prompt.  Checks:
  all complete, none shed or failed, twin streams identical, every stream
  agrees with a teacher-forced forward pass of the model, `serving.decode` and
  `serving.prefill_chunk` compiled exactly once (strict watchdog), no page
  left mapped.

`--chips 4` adds, on one four-chip host: the serve phase again through
`DecodeEngine(tp=2)` and `tp=4` (same checks; how many streams equal the
one-chip phase's is reported), and the
dp2 x mp2 hybrid `TrainStep` (`init_mesh` + `parallelize`) with the flash kernel
partitioned by shard_map — per-shard kernel operands, no all-gather into it —
and the candidates of the one autotune family that spans devices (the
collective-matmul ring).  `--kernels` runs every other registered autotune
candidate of every kernel family once at its standard key; a refusal fails.

Without a TPU the script fails at once; it never swaps in a smaller model.
`--rehearse` is the one exception and has to be asked for: the same code at a
tiny size on CPU with the kernels in the Pallas interpreter, every line it
prints labelled `"rehearsal": true`.

Every line on stdout is one JSON object: one per phase, a summary that ends
with `"claim": null`, and last the result, which holds exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}` with
the device as jax reports it (`"ok": false` and a non-zero exit when a phase
failed; no result line at all when there is no TPU).  Times are printed so
that a cold and a warm run of the compile cache can be told apart; no rate is
computed and none is a benchmark result.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import gc
import json
import math
import os
import re
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.analysis.trace.core import walk_eqns
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.parallel_base import parallelize
from paddle_tpu.jit import TrainStep, functional_call
from paddle_tpu.kernels import autotune
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import flash_attention_pallas as fap
from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
from paddle_tpu.serving import loadgen
from paddle_tpu.serving.engine import DecodeEngine
from paddle_tpu.serving.frontend import ServingFrontend
from paddle_tpu.utils.compile_cache import enable_compile_cache

TRAIN_STEPS = 6
N_REQUESTS = 8
OFFERED_QPS = 4.0
OFFER_TIMEOUT_SECONDS = 600.0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="python chip_smoke.py",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 adds the tensor-parallel serving and hybrid "
                         "dp2 x mp2 training phases (needs four TPU devices)")
    ap.add_argument("--kernels", action="store_true",
                    help="also run every autotune candidate of every kernel "
                         "family once at its standard key")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model's depth (default: all 24 layers)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny model on CPU, kernels interpreted; proves the "
                         "script, not the chip")
    return ap.parse_args(argv)


class Sizes:
    """The one place the rehearsal differs from the real run."""

    def __init__(self, rehearse: bool, layers):
        if rehearse:
            self.cfg = GPTConfig(vocab_size=512, max_position_embeddings=512,
                                 hidden_size=256, num_hidden_layers=2,
                                 num_attention_heads=4, intermediate_size=512)
            self.batch, self.seq = 4, 128
            self.slots, self.max_len, self.page = 4, 512, 64
            self.amp = False
        else:
            self.cfg = GPTConfig.gpt2_medium()
            self.batch, self.seq = 8, 1024
            self.slots, self.max_len, self.page = 8, 1024, 64
            self.amp = True
        if layers is not None:
            self.cfg.num_hidden_layers = int(layers)
        self.cfg.hidden_dropout_prob = 0.0
        self.cfg.attention_dropout_prob = 0.0


def emit(rehearse: bool, **fields):
    if rehearse:
        fields = {"rehearsal": True, **fields}
    print(json.dumps(fields), flush=True)


def check(cond, what: str):
    if not cond:
        raise AssertionError("chip_smoke: " + what)


def peak_hbm_bytes(device):
    stats = device.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def build_model(sizes: Sizes, seed: int):
    paddle.seed(seed)
    model = GPTForCausalLM(sizes.cfg)
    if sizes.amp:
        paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    return model


def build_step(model):
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-4, weight_decay=0.01)
    return TrainStep(model, lambda logits, labels: crit(logits, labels), opt)


def fixed_batch(sizes: Sizes, seed: int):
    ids = np.random.RandomState(seed).randint(
        0, sizes.cfg.vocab_size, (sizes.batch, sizes.seq)).astype(np.int32)
    return jnp.asarray(ids)


def pallas_calls(traced):
    """The pallas_call equations of a traced program, shard_map bodies
    included."""
    return [site.eqn for site in walk_eqns(traced.jaxpr, into_pallas=False)
            if site.eqn.primitive.name == "pallas_call"]


def run_steps(step, x, vocab: int):
    """First call (compile + run), then the remaining steps; returns
    (losses, first_call_seconds, later_steps_seconds)."""
    t0 = time.perf_counter()
    losses = [float(step(x, x).numpy())]
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    later = [step(x, x) for _ in range(TRAIN_STEPS - 1)]
    losses += [float(l.numpy()) for l in later]
    t_later = time.perf_counter() - t0
    check(all(math.isfinite(l) for l in losses),
          "non-finite training loss: %r" % (losses,))
    check(abs(losses[0] - math.log(vocab)) < 1.0,
          "first loss %.3f is not near ln(vocab) = %.3f for a model with "
          "random weights" % (losses[0], math.log(vocab)))
    check(losses[-1] < losses[0],
          "loss did not fall on a fixed batch: %r" % (losses,))
    return losses, t_first, t_later


# -- phase: flash kernel against the reference --------------------------------

def phase_flash_reference(rehearse: bool):
    """Forward and backward of the flash kernel against the O(S^2) reference
    on a small input — the repo's own parity check, run where the kernel
    will run."""
    b, s, h, d = 2, 256, 4, 64
    dtype = jnp.float32 if rehearse else jnp.bfloat16
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
               for _ in range(3))
    check(fa.supported(q, k), "flash kernel refuses its own standard shape")

    def flash_loss(q, k, v):
        o = fa.flash_attention_bshd(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    def ref_loss(q, k, v):
        o = fap._reference_bhsd(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)),
                                True, 1.0 / math.sqrt(d))
        o = jnp.swapaxes(o, 1, 2)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                                has_aux=True))
    (_, out), grads = grad(flash_loss)(q, k, v)
    (_, out_ref), grads_ref = grad(ref_loss)(q, k, v)
    tol = 2e-4 if rehearse else 3e-2       # bf16: 8 mantissa bits
    err = {"out": float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                        - out_ref.astype(jnp.float32))))}
    for name, g, gr in zip(("dq", "dk", "dv"), grads, grads_ref):
        scale = float(jnp.max(jnp.abs(gr.astype(jnp.float32)))) or 1.0
        err[name] = float(jnp.max(jnp.abs(
            g.astype(jnp.float32) - gr.astype(jnp.float32)))) / scale
    check(all(e < tol for e in err.values()),
          "flash kernel disagrees with the reference: %r (tol %g)"
          % (err, tol))
    emit(rehearse, phase="flash_reference", max_error=err, tolerance=tol)


# -- phase: train ---------------------------------------------------------------

def phase_train(sizes: Sizes, args, device):
    t0 = time.perf_counter()
    model = build_model(sizes, args.seed)
    step = build_step(model)
    x = fixed_batch(sizes, args.seed)
    n_kernels = len(pallas_calls(step._step.trace(*step.trace_args((x, x)))))
    t_setup = time.perf_counter() - t0
    want = 2 * sizes.cfg.num_hidden_layers          # flash fwd + bwd a layer
    check(n_kernels == want,
          "the train step holds %d Pallas calls, expected %d: attention did "
          "not take the flash kernel" % (n_kernels, want))
    losses, t_first, t_later = run_steps(step, x, sizes.cfg.vocab_size)
    counts = obs.compile_counts()
    check(counts.get("jit.train_step") == 1,
          "jit.train_step compile count %r, expected 1" % (counts,))
    step.sync_to_model()
    emit(args.rehearse, phase="train", layers=sizes.cfg.num_hidden_layers,
         batch=[sizes.batch, sizes.seq], pallas_calls=n_kernels,
         losses=[round(l, 4) for l in losses],
         build_and_trace_seconds=round(t_setup, 2),
         first_step_seconds=round(t_first, 2),
         later_steps_seconds=round(t_later, 2), steps=TRAIN_STEPS,
         compile_counts=counts, peak_hbm_bytes=peak_hbm_bytes(device))
    return model


# -- phase: serve ---------------------------------------------------------------

def serve_plan(sizes: Sizes, seed: int):
    """Eight greedy requests from a seed; request 5 repeats request 0."""
    rng = np.random.default_rng(seed)
    plan, t = [], 0.0
    for _ in range(N_REQUESTS):
        plen = int(rng.integers(130, 193))
        plan.append((t, {
            "prompt": [int(tok) for tok in
                       rng.integers(0, sizes.cfg.vocab_size, (plen,))],
            "max_new_tokens": int(rng.integers(32, 65)),
            "temperature": 0.0}))
        t += float(rng.exponential(1.0 / OFFERED_QPS))
    plan[5] = (plan[5][0], dict(plan[0][1]))
    return plan


def reference_logits_fn(model):
    state = model.functional_state()

    # the weights are an argument: closed over, they would be compiled in as
    # constants (a 0.7 GB program, and as much again in the compile cache)
    @jax.jit
    def forward(state, ids):
        out, _ = functional_call(model, state, paddle.Tensor(ids))
        return out.astype(jnp.float32)
    return lambda ids: forward(state, ids)


def check_against_forward(logits_of, plan, streams):
    """Teacher forcing: at every position the token the engine chose must
    be, to the reference forward pass, as good as its own best token up to
    rounding.  An argmax comparison would flake — with random weights the
    top two logits of 50k sit about a bf16 rounding apart — so the margin
    is a tenth of the spread between the best logit and the mean, two
    orders above rounding and far below the deficit of a wrong token."""
    width = 256                      # every prompt + stream fits; one compile
    worst = 0.0
    for (_, payload), stream in zip(plan, streams):
        ids = payload["prompt"] + stream
        check(len(ids) <= width, "stream longer than the reference window")
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(ids)] = ids
        logits = np.asarray(logits_of(jnp.asarray(padded)))[0]
        first = len(payload["prompt"]) - 1       # predicts stream[0]
        rows = logits[first:first + len(stream)]
        best = rows.max(axis=-1)
        chosen = rows[np.arange(len(stream)), np.asarray(stream)]
        deficit = (best - chosen) / (best - rows.mean(axis=-1))
        worst = max(worst, float(deficit.max()))
    check(worst < 0.1,
          "a served token is %.3f of the logit spread below the reference "
          "forward pass's best token" % worst)
    return worst


def offer_sync(host, port, plan):
    """`loadgen.offer` from synchronous code, bounded: a server that stopped
    answering fails the smoke, it does not hang the chip."""
    return asyncio.run(asyncio.wait_for(loadgen.offer(host, port, plan),
                                        timeout=OFFER_TIMEOUT_SECONDS))


def phase_serve(sizes: Sizes, args, model, name: str, logits_of,
                tp: int = 1, expect_streams=None):
    model.eval()
    engine = DecodeEngine(model, num_slots=sizes.slots, max_len=sizes.max_len,
                          page_size=sizes.page, seed=args.seed, tp=tp)
    frontend = ServingFrontend(engine, queue_limit=32)
    host, port = frontend.start()
    plan = serve_plan(sizes, args.seed)
    try:
        # one request first: it pays the compiles, so that the drive below
        # runs on warm programs and the two times can be told apart
        t0 = time.perf_counter()
        warm, _ = offer_sync(host, port, [(0.0, {
            "prompt": plan[1][1]["prompt"], "max_new_tokens": 4,
            "temperature": 0.0})])
        t_first = time.perf_counter() - t0
        check(warm[0]["status"] == 200 and warm[0]["tokens"] == 4,
              "warm-up request failed: %r" % (warm[0],))
        recs, wall = offer_sync(host, port, plan)
    finally:
        frontend.stop()
    summary = loadgen.summarize(recs, wall, qps=OFFERED_QPS, mix="smoke")
    check(summary["sent"] == summary["completed"] == N_REQUESTS
          and not (summary["shed"] or summary["errors"]
                   or summary["dropped_streams"]),
          "%s: not every request completed: %r" % (name, summary))
    streams = [r["token_ids"] for r in recs]
    for (_, payload), stream in zip(plan, streams):
        check(len(stream) == payload["max_new_tokens"],
              "%s: a stream delivered %d tokens of %d"
              % (name, len(stream), payload["max_new_tokens"]))
    check(streams[0] == streams[5],
          "%s: the same greedy prompt gave two different streams" % name)
    counts = {k: v for k, v in obs.compile_counts().items()
              if k.startswith("serving.") and v}
    check(counts.get("serving.decode") == 1
          and counts.get("serving.prefill_chunk") == 1,
          "%s: serving compile counts %r, expected decode and prefill_chunk "
          "once each" % (name, counts))
    check(engine._alloc.pages_used() == 0,
          "%s: %d pages still mapped after the drain"
          % (name, engine._alloc.pages_used()))
    t0 = time.perf_counter()
    fields = {"worst_deficit_vs_forward": round(
        check_against_forward(logits_of, plan, streams), 4)}
    fields["forward_check_seconds"] = round(time.perf_counter() - t0, 2)
    if expect_streams is not None:
        # reported, not required: a row-parallel matmul adds tp partial
        # sums where one chip accumulates in one pass, the last bits differ,
        # and a greedy stream forks wherever its top two logits sit within
        # that rounding (equal 8/8 at 24 layers, 1/8 at 2 on the v5e).
        # What is required of a sharded stream is the forward check above.
        same = sum(a == b for a, b in zip(streams, expect_streams))
        fields["streams_equal_to_one_chip"] = "%d/%d" % (same, N_REQUESTS)
    if tp > 1:
        placed = {d for leaf in jax.tree_util.tree_leaves(engine.state)
                  for d in leaf.sharding.device_set}
        check(placed == set(jax.devices()[:tp]),
              "%s: weights live on %r, expected the first %d devices"
              % (name, sorted(d.id for d in placed), tp))
    emit(args.rehearse, phase=name, tp=tp, requests=N_REQUESTS,
         completed=summary["completed"], shed=summary["shed"],
         errors=summary["errors"], tokens=summary["goodput_tokens"],
         prompt_lengths=[len(p["prompt"]) for _, p in plan],
         first_request_seconds=round(t_first, 2),
         drive_seconds=round(wall, 2), compile_counts=counts,
         peak_hbm_bytes=peak_hbm_bytes(jax.devices()[0]), **fields)
    # the watchdog sums same-name entries over LIVE engines: drop this one
    # before the next phase builds its own
    del frontend, engine
    gc.collect()
    return streams


# -- phase: hybrid dp2 x mp2 training (--chips 4) --------------------------------

#: "%name = <type> opcode(%operand, %operand...)" of one HLO instruction; a
#: tuple type's parentheses hold shapes, never %-names, so the first
#: parenthesised run of %-names is the operand list
_HLO_DEF = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s(.*)$")
_HLO_CALL = re.compile(r"\s([a-z][\w\-]*)\(((?:%[\w.\-]+(?:, )?)+)\)")


def compiled_custom_call_check(hlo: str, want_shape: str):
    """From the compiled HLO: every Mosaic call takes per-shard operands and
    nothing within three producers of it is an all-gather."""
    ops = {}                           # name -> (opcode, operands, text)
    for line in hlo.splitlines():
        d = _HLO_DEF.match(line)
        if d:
            c = _HLO_CALL.search(d.group(2))
            ops[d.group(1)] = (c.group(1) if c else "",
                               c.group(2).split(", ") if c else [],
                               d.group(2))
    calls = [op for op in ops.values()
             if 'custom_call_target="tpu_custom_call"' in op[2]]
    check(calls, "no Mosaic call in the compiled hybrid step")
    for _, operands, text in calls:
        check(want_shape in text,
              "a Mosaic call does not take per-shard %s operands: %s"
              % (want_shape, text[:200]))
        for _ in range(3):
            producers = [ops[o] for o in operands if o in ops]
            check(not any(p[0].startswith("all-gather") for p in producers),
                  "an all-gather feeds a Mosaic call")
            operands = [o for p in producers for o in p[1]]
    return len(calls)


def phase_hybrid_train(sizes: Sizes, args):
    devices = jax.devices()[:4]
    mesh = mesh_mod.init_mesh({"dp": 2, "mp": 2}, devices=devices)
    try:
        model = build_model(sizes, args.seed)
        parallelize(model)
        step = build_step(model)
        x = jax.device_put(fixed_batch(sizes, args.seed),
                           jax.sharding.NamedSharding(
                               mesh, jax.sharding.PartitionSpec("dp", None)))
        traced = step._step.trace(*step.trace_args((x, x)))
        kernels = pallas_calls(traced)
        want = 2 * sizes.cfg.num_hidden_layers
        check(len(kernels) == want,
              "the hybrid step holds %d Pallas calls, expected %d"
              % (len(kernels), want))
        shard = (sizes.batch // 2, sizes.seq, sizes.cfg.hidden_size // 2)
        for eqn in kernels:
            check(tuple(eqn.invars[0].aval.shape) == shard,
                  "a flash call in the hybrid step takes %r, expected the "
                  "per-shard %r" % (tuple(eqn.invars[0].aval.shape), shard))
        mosaic_calls = None
        if not args.rehearse:
            # interpreted kernels leave no custom call to inspect
            mosaic_calls = compiled_custom_call_check(
                traced.lower().compile().as_text(),
                "bf16[%d,%d,%d]" % shard)
        losses, t_first, t_later = run_steps(step, x, sizes.cfg.vocab_size)
        counts = obs.compile_counts()
        check(counts.get("jit.train_step") == 1,
              "hybrid jit.train_step compile count %r, expected 1" % (counts,))
        placed = {d for leaf in jax.tree_util.tree_leaves(step.params)
                  for d in leaf.sharding.device_set}
        check(placed == set(devices),
              "hybrid parameters live on %d devices, expected 4" % len(placed))
        emit(args.rehearse, phase="hybrid_train", mesh={"dp": 2, "mp": 2},
             layers=sizes.cfg.num_hidden_layers, pallas_calls=len(kernels),
             per_shard_operand=list(shard), mosaic_calls=mosaic_calls,
             losses=[round(l, 4) for l in losses],
             first_step_seconds=round(t_first, 2),
             later_steps_seconds=round(t_later, 2), compile_counts=counts,
             peak_hbm_bytes=[peak_hbm_bytes(d) for d in devices])
    finally:
        mesh_mod.set_mesh(None)


# -- phase: every kernel candidate (--kernels) ------------------------------------

def kernel_pairs(rehearse: bool):
    if not rehearse:
        return autotune.standard_keys()
    # the families at shapes the interpreter finishes in seconds
    from paddle_tpu.distributed import mp_overlap
    from paddle_tpu.kernels import decode_attention
    flash = fap.autotune_key(b=1, s=256, sk=256, h=4, d=64, dtype="float32",
                             causal=True)
    return [("flash_fwd", flash), ("flash_bwd", flash),
            ("flash_bwd_dq", flash), ("flash_bwd_dkv", flash),
            ("decode_attn_paged", decode_attention.paged_autotune_key(
                slots=2, pages=8, page_size=16, max_pages=4, h=2, d=64,
                qlen=1, dtype="float32")),
            ("mp_overlap", mp_overlap.autotune_key(
                kind="row", m=8, k=64, n=32, n_dev=2, dtype="float32"))]


def phase_kernels(args, multi_device: bool):
    """Run every candidate once.  ``multi_device`` selects the families
    whose standard key spans several devices (the collective-matmul rings)
    — they belong to the --chips 4 run; --kernels covers the rest."""
    pairs = kernel_pairs(args.rehearse)     # imports the kernel modules,
    families = autotune.families()          # which register the families
    ran, skipped, refused = 0, [], []
    t0 = time.perf_counter()
    for fam_name, key in pairs:
        fam = families[fam_name]
        if (key.get("n_dev", 1) > 1) != multi_device:
            if not multi_device:
                skipped.append("%s: spans %d devices, runs under --chips 4"
                               % (fam_name, key["n_dev"]))
            continue
        try:
            for cand in fam.candidates(key):
                sig = "%s[%s] %s" % (fam_name, autotune.key_str(key),
                                     autotune._cand_sig(cand))
                rejected = autotune._vmem_reject(fam, cand, key)
                if rejected:
                    refused.append("%s: %s" % (sig, rejected))
                    continue
                try:
                    fam.runner(cand, key)()
                    ran += 1
                except Exception as e:  # collected, then raised below
                    refused.append("%s: %s: %s" % (
                        sig, type(e).__name__, str(e).strip()[:300]))
        finally:
            if fam.cleanup is not None:
                fam.cleanup(key)
    check(not refused, "%d kernel candidate(s) refused:\n  %s"
          % (len(refused), "\n  ".join(refused)))
    emit(args.rehearse,
         phase="kernels_multi_device" if multi_device else "kernels",
         candidates_run=ran, skipped=skipped,
         seconds=round(time.perf_counter() - t0, 2))


# -- main -------------------------------------------------------------------------

def main(argv=None):
    args = parse_args(argv)
    # any second compile of a compile-once entry raises instead of warning
    os.environ["PADDLE_TPU_STRICT_COMPILE"] = "1"
    if args.rehearse:
        # asked for by argument, never inferred; before the backend starts
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.chips)
    if not args.rehearse and jax.default_backend() != "tpu":
        sys.exit("chip_smoke: no TPU: the jax backend here is %r.  This "
                 "script runs on the chip (python chip_smoke.py through the "
                 "chip tool); only --rehearse runs on a CPU, and says so."
                 % jax.default_backend())
    devices = jax.devices()
    if len(devices) < args.chips:
        sys.exit("chip_smoke: --chips %d needs %d %s devices, jax shows %d"
                 % (args.chips, args.chips, devices[0].platform,
                    len(devices)))
    cache_dir = enable_compile_cache()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse:
        # the peak table must know this part: an unknown kind raises here
        # instead of surfacing later as a null utilization
        obs.costs.peak_flops(device["kind"])
    emit(args.rehearse, phase="start", device=device,
         compile_cache_dir=cache_dir, jax=jax.__version__)
    sizes = Sizes(args.rehearse, args.layers)
    t0 = time.perf_counter()
    scope = (fa.interpret_scope() if args.rehearse
             else contextlib.nullcontext())
    try:
        with scope:
            run_phases(sizes, args, devices)
    except BaseException:
        # the traceback and the non-zero exit follow; the result says so too
        emit(args.rehearse, ok=False, device=device)
        raise
    emit(args.rehearse, phase="summary", compile_cache_dir=cache_dir,
         chips=args.chips, kernels=args.kernels,
         layers=sizes.cfg.num_hidden_layers,
         total_seconds=round(time.perf_counter() - t0, 1), claim=None)
    # the result: last line of stdout, these two keys and no others
    emit(args.rehearse, ok=True, device=device)


def run_phases(sizes: Sizes, args, devices):
    phase_flash_reference(args.rehearse)
    model = phase_train(sizes, args, devices[0])
    gc.collect()           # the TrainStep's state is held by closure cycles
    logits_of = reference_logits_fn(model)
    streams = phase_serve(sizes, args, model, "serve", logits_of)
    if args.chips == 4:
        phase_serve(sizes, args, model, "serve_tp2", logits_of, tp=2,
                    expect_streams=streams)
        phase_serve(sizes, args, model, "serve_tp4", logits_of, tp=4,
                    expect_streams=streams)
        del model, logits_of
        gc.collect()
        phase_hybrid_train(sizes, args)
        phase_kernels(args, multi_device=True)
    if args.kernels:
        phase_kernels(args, multi_device=False)


if __name__ == "__main__":
    main()
