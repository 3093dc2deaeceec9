"""The ``kind: train`` runner: the program's compiled training step on
batches drawn from the seed, timed over a window of ``--seconds``.

The step is built as ``chip_smoke.py::build_model/build_step`` builds it
(``amp.decorate`` O2 bf16, ``AdamW``, ``jit.TrainStep``); with a ``mesh`` in
the traffic file, under ``init_mesh`` + ``parallelize`` as
``chip_smoke.py::phase_hybrid_train`` does.  Nothing that depends on the seed
is closed over by a jitted function: weights, batches, key and learning rate
are arguments, so two seeds run one program.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp

from benchmarks.lib import check, harness, seeds, weights as weights_mod

WARMUP_STEPS = 2
TRACE_AFTER_STEPS = 3
IN_FLIGHT = 2


@functools.partial(jax.jit, static_argnames=("ring", "batch", "seq", "limit"))
def _make_batches(words, ring, batch, seq, limit):
    key = jax.random.wrap_key_data(words.astype(jnp.uint32),
                                   impl="threefry2x32")
    ids = jax.random.randint(key, (ring, batch, seq), 0, limit, jnp.int32)
    return tuple(ids[i] for i in range(ring))


def _place_like(new, old):
    """``new`` where ``old`` lives, when that is a mesh; single-device
    arrays stay uncommitted, as the step's own outputs are."""
    sharding = getattr(old, "sharding", None)
    if isinstance(sharding, jax.sharding.NamedSharding):
        return jax.device_put(new, sharding)
    return new


def _reset_to_seed(step, seed):
    """Put the step's state back to the seed's weights and a fresh
    optimizer, in the layout and types it had: the window then starts from
    the same point whatever the warm-up did, and no program is compiled.
    The old state is dropped first, so that two copies never live at once
    (``peak_bytes_in_use`` is a lifetime peak and should stay the step's)."""
    described = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        tree)
    params_like, opt_like = described(step.params), described(step.opt_state)
    step.params = step.opt_state = None
    like = {k: jax.ShapeDtypeStruct(
        v.shape, step._compute_dtypes.get(k, v.dtype))
        for k, v in params_like.items()}
    cfg = step.model.config
    made = weights_mod.make_weights(
        seeds.key_words(seed, "weights"), like, cfg.initializer_range,
        cfg.num_hidden_layers)
    step.params = {k: _place_like(made.pop(k).astype(v.dtype), v)
                   for k, v in params_like.items()}
    step.opt_state = jax.tree_util.tree_map(
        _place_like, step.optimizer.init_state(step.params), opt_like)


def _timed_window(step, batches, seconds, trace_steps):
    """Dispatch steps for ``seconds`` with at most IN_FLIGHT unfinished, and
    wait for the last.  Returns (losses, window seconds, seconds the clock
    was paused, reduced trace or None).

    With ``trace_steps`` the profiler covers that many steps after the
    first TRACE_AFTER_STEPS, and the clock stops while it starts and while
    it writes its trace out: the device is idle on both sides of either
    (every step dispatched has finished), so what is left is the time an
    untraced run would have taken for the same steps."""
    profiler = harness.Profiler() if trace_steps else None
    trace, losses, paused = None, [], 0.0
    ring = len(batches)
    t0 = time.perf_counter()
    while True:
        n = len(losses)
        if profiler and n in (TRACE_AFTER_STEPS,
                              TRACE_AFTER_STEPS + trace_steps):
            jax.block_until_ready(losses[-1]._array)
            t_pause = time.perf_counter()
            if n == TRACE_AFTER_STEPS:
                profiler.start()
            else:
                trace, profiler = profiler.stop_and_reduce(), None
            paused += time.perf_counter() - t_pause
        if (profiler is None
                and time.perf_counter() - t0 - paused >= seconds):
            break
        with jax.profiler.TraceAnnotation("bench.step_call"):
            x = batches[n % ring]
            losses.append(step(x, x))
        if n >= IN_FLIGHT:
            with jax.profiler.TraceAnnotation("bench.step_wait"):
                jax.block_until_ready(losses[n - IN_FLIGHT]._array)
    jax.block_until_ready(losses[-1]._array)
    return losses, time.perf_counter() - t0 - paused, paused, trace


def run(run: harness.Run, config: dict, traffic: dict, devices):
    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.parallel_base import parallelize
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPTPretrainingCriterion

    args = run.args
    mesh_axes = traffic.get("mesh")
    mesh = None
    if mesh_axes:
        n = math.prod(mesh_axes.values())
        mesh = mesh_mod.init_mesh(dict(mesh_axes), devices=devices[:n])
    try:
        model, cfg, _ = harness.build_model(run, config, traffic["amp"])
        if mesh is not None:
            parallelize(model)
        crit = GPTPretrainingCriterion()
        opt = paddle.optimizer.AdamW(
            parameters=model.parameters(),
            learning_rate=traffic["learning_rate"],
            weight_decay=traffic["weight_decay"])
        step = TrainStep(model, lambda logits, labels: crit(logits, labels),
                         opt)
        batch, seq, ring = traffic["batch"], traffic["seq"], traffic["ring"]
        batches = _make_batches(
            jnp.asarray(seeds.key_words(args.seed, "batches")), ring, batch,
            seq, min(config["token_id_limit"], cfg.vocab_size))
        if mesh is not None:
            spec = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("dp", None))
            batches = tuple(jax.device_put(b, spec) for b in batches)
        run.part("step_build")

        warm_seconds = []
        for i in range(WARMUP_STEPS):
            t_step = time.perf_counter()
            step(batches[i % ring], batches[i % ring]).numpy()
            warm_seconds.append(round(time.perf_counter() - t_step, 3))
        run.emit(phase="setup", part="warmup_steps", seconds=warm_seconds)
        run.part("warmup")
        _reset_to_seed(step, args.seed)
        jax.block_until_ready(step.params)
        run.part("reset_to_seed")
        compiles_before = obs.compile_counts()
        run.setup_done()

        # -- the window ------------------------------------------------------
        losses, window_s, paused, trace = _timed_window(
            step, batches, args.seconds,
            traffic["trace_steps"] if args.trace else 0)
        n = len(losses)
        # -- read what the window left, before the check touches the device --
        device = harness.device_record(devices, run.record["chips"])
        compiles_after = obs.compile_counts()
        registry = obs.default_registry().snapshot()
        loss_values = [float(l.numpy()) for l in losses]
        tokens = n * batch * seq
        run.emit(phase="window", steps=n, batch=[batch, seq],
                 window_s=window_s, first_loss=loss_values[0],
                 last_loss=loss_values[-1], clock_paused_s=paused,
                 programs_in_window=run.programs_in_window())
        run.record.update(
            kind="train", window_s=window_s, steps=n, tokens=tokens,
            attempted=n, batch=batch, seq=seq, config=config["gpt_config"],
            failed=sum(not math.isfinite(v) for v in loss_values),
            compiles_before=compiles_before, compiles_after=compiles_after,
            device=device, device_kind=device["kind"],
            end_to_end={"train_tokens_per_s": tokens / window_s})

        # -- the check: after the window, outside every timed interval -------
        t_check = time.perf_counter()
        verdict = _check(step, model, cfg, first_batch=batches[0],
                         loss_values=loss_values,
                         with_gradients=bool(args.trace))
        run.emit(phase="check", seconds=round(time.perf_counter() - t_check,
                                              3), **verdict)
        run.record["correct"] = bool(verdict["within"])
        return registry, trace
    finally:
        if mesh is not None:
            mesh_mod.set_mesh(None)


def _one_device(x):
    """``x`` on the first of its devices (the reference runs on one chip;
    an array that lives on a mesh is gathered there, device to device)."""
    if isinstance(getattr(x, "sharding", None), jax.sharding.NamedSharding):
        return jax.device_put(x, sorted(x.sharding.device_set,
                                        key=lambda d: d.id)[0])
    return x


def _check(step, model, cfg, first_batch, loss_values, with_gradients):
    """System against reference on the seed's weights: the first timed
    step's loss, the eval logits of two sequences, every loss finite; in
    the traced run the per-tensor gradients too."""
    # the model object still holds the seed's weights in the types they
    # are computed in (TrainStep trains a copy of its own)
    clock = harness.PartClock()
    state = model.functional_state()
    ref_forward = check.reference_forward_fn(
        cfg.num_hidden_layers, cfg.num_attention_heads,
        cfg.layer_norm_epsilon)
    # the system's side stays where the system runs (on a mesh, sharded);
    # the reference's side is gathered onto one chip
    sample_sys = first_batch[:2]
    sys_logits = jax.block_until_ready(_one_device(
        check.system_forward_fn(model)(state, sample_sys)))
    clock.part("system_forward")
    weights = {k: _one_device(v) for k, v in state.items()}
    first_batch = _one_device(first_batch)
    sample = first_batch[:2]
    ref_loss = check.reference_loss(ref_forward, weights, first_batch)
    loss_rel = abs(loss_values[0] - ref_loss) / abs(ref_loss)
    clock.part("reference_loss")
    errors = check.logits_errors(sys_logits, ref_forward(weights, sample))
    clock.part("logits_compare")
    finite = all(math.isfinite(v) for v in loss_values)
    verdict = {
        "kind": "train", "loss_first_step": loss_values[0],
        "loss_reference": ref_loss, "loss_rel_err": loss_rel,
        "logits": errors, "losses_finite": finite, "parts_s": clock.parts,
        "tolerance": {"loss_rel": check.LOSS_REL_TOL,
                      "logits_rel_rms": check.LOGITS_RMS_TOL}}
    within = (finite and errors["finite"]
              and loss_rel < check.LOSS_REL_TOL
              and errors["rel_rms"] < check.LOGITS_RMS_TOL)
    if with_gradients:
        grads = _gradient_check(step, cfg, weights, sample_sys, sample)
        verdict["gradients"] = grads
        verdict["tolerance"]["grad_rel"] = check.GRAD_REL_TOL
        within = within and grads["worst"] < check.GRAD_REL_TOL
    verdict["within"] = bool(within)
    return verdict


def _gradient_check(step, cfg, weights, sample_sys, sample):
    """Per-tensor gradients of the system's own loss-and-grad computation
    (``TrainStep._grads_core``, the one the compiled step runs) against
    ``jax.grad`` of the reference's loss, on two sequences, at the seed's
    weights.  Each part's seconds are printed."""
    from benchmarks.reference import gpt_ref
    t0 = time.perf_counter()
    params = {k: _place_like(weights[k].astype(v.dtype), v)
              for k, v in step.params.items()}
    _, _, sys_grads = jax.jit(step._grads_core)(
        params, step.buffers, jax.random.key(0), (sample_sys, sample_sys))
    sys_grads = jax.block_until_ready(
        {k: _one_device(v) for k, v in sys_grads.items()})
    t1 = time.perf_counter()
    ref_grads = jax.jit(jax.grad(functools.partial(
        gpt_ref.loss, num_layers=cfg.num_hidden_layers,
        num_heads=cfg.num_attention_heads, eps=cfg.layer_norm_epsilon)))(
            {k: _one_device(v) for k, v in params.items()}, sample)
    jax.block_until_ready(ref_grads)
    t2 = time.perf_counter()
    out = check.grad_errors(sys_grads, ref_grads)
    out["seconds"] = {"system": round(t1 - t0, 3),
                      "reference": round(t2 - t1, 3),
                      "compare": round(time.perf_counter() - t2, 3)}
    return out
