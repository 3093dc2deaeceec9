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
import sys
import threading
import time

import jax
import jax.numpy as jnp

from benchmarks.lib import check, harness, seeds, weights as weights_mod

WARMUP_STEPS = 2
TRACE_AFTER_STEPS = 3
IN_FLIGHT = 2
HEARTBEAT_S = 0.02


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


def _release_state(step):
    """Drop the step's parameters and optimizer state, and return what
    describes them: ``(params, opt_state)`` with every array replaced by
    its shape, dtype and sharding.  Nothing else holds the arrays (the
    step donates them from call to call), so their memory is free when
    this returns."""
    described = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        tree)
    params_like, opt_like = described(step.params), described(step.opt_state)
    step.params = step.opt_state = None
    return params_like, opt_like


def _reset_to_seed(step, seed, config):
    """Put the step's state back to the seed's weights and a fresh
    optimizer, in the layout and types it had: the window then starts from
    the same point whatever the warm-up did, and no program is compiled.
    The old state is dropped first, so that two copies never live at once
    (``peak_bytes_in_use`` is a lifetime peak and should stay the step's)."""
    params_like, opt_like = _release_state(step)
    like = {k: jax.ShapeDtypeStruct(
        v.shape, step._compute_dtypes.get(k, v.dtype))
        for k, v in params_like.items()}
    made = weights_mod.make_weights(
        seeds.key_words(seed, "weights"), like, config["family"],
        config["model"])
    step.params = {k: _place_like(made.pop(k).astype(v.dtype), v)
                   for k, v in params_like.items()}
    step.opt_state = jax.tree_util.tree_map(
        _place_like, step.optimizer.init_state(step.params), opt_like)


class _Heartbeat:
    """A thread that does nothing but wake every HEARTBEAT_S seconds and
    note the host's clock.  Some windows lose seconds to one wait for the
    device with nothing compiling (PERF.md section 7); a host that was not
    scheduled meanwhile (the machine paused, the process stopped) shows as
    a gap between two wakes as long as the wait, a device or runtime that
    stalled under a live host does not.  The waits it sleeps through hold
    no lock: the main thread waits for the device outside the GIL."""

    def __enter__(self):
        self.wakes = [time.perf_counter()]
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True,
                                        name="bench-heartbeat")
        self._thread.start()
        return self

    def _beat(self):
        while not self._stop.wait(HEARTBEAT_S):
            self.wakes.append(time.perf_counter())

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=5.0)


def _longest_gaps(wakes, t0, count=3):
    """``[[seconds into the window, seconds between two wakes], ...]``,
    longest first: HEARTBEAT_S and a little in a window whose host ran."""
    gaps = sorted(((b - a, a - t0) for a, b in zip(wakes, wakes[1:])),
                  reverse=True)[:count]
    return [[round(at, 4), round(gap, 4)] for gap, at in gaps]


def _timed_window(step, batches, seconds, trace_steps):
    """Dispatch steps for ``seconds`` with at most IN_FLIGHT unfinished, and
    wait for the last.  Returns (losses, window seconds, seconds the clock
    was paused, reduced trace or None, the host's clock at the start, after
    the dispatch and after the wait of every iteration, the heartbeat's
    longest gaps).

    With ``trace_steps`` the profiler covers that many steps after the
    first TRACE_AFTER_STEPS, and the clock stops while it starts and while
    it writes its trace out: the device is idle on both sides of either
    (every step dispatched has finished), so what is left is the time an
    untraced run would have taken for the same steps."""
    profiler = harness.Profiler() if trace_steps else None
    trace, losses, paused, stamps = None, [], 0.0, []
    ring = len(batches)
    with _Heartbeat() as heartbeat:
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
            t_start = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step_call"):
                x = batches[n % ring]
                losses.append(step(x, x))
            t_called = time.perf_counter()
            if n >= IN_FLIGHT:
                with jax.profiler.TraceAnnotation("bench.step_wait"):
                    jax.block_until_ready(losses[n - IN_FLIGHT]._array)
            stamps.append((t_start, t_called, time.perf_counter()))
        jax.block_until_ready(losses[-1]._array)
        window_s = time.perf_counter() - t0 - paused
    return (losses, window_s, paused, trace, stamps,
            _longest_gaps(heartbeat.wakes, t0))


def _step_program_bytes(step, batch):
    """The memory analysis of the compiled step the window drove
    (``harness.program_bytes``).  JAX hands out no executable that a jit
    call built, so the step is lowered again for the arguments it ran
    with: tracing, lowering and the executable are found again in JAX's
    own caches while the step lives (what ``observability.scopes.index()``
    relies on too), else in the persistent cache.  After the window and
    its reads, never inside ``setup_s``; None where that fails."""
    try:
        return harness.program_bytes(
            step._step.lower(*step.trace_args((batch, batch))).compile())
    except Exception as e:      # a reading, never the end of a traced run
        print("benchmark: no memory analysis of the step: %r" % e,
              file=sys.stderr)
        return None


def _slowest_iterations(stamps, count=3):
    """``[[index, seconds to the next iteration's start, of which in the
    dispatch, of which waiting for the device], ...]``, longest first: where
    a run that reads far off lost its time.  A steady run waits one step an
    iteration and dispatches in milliseconds."""
    rows = [[i, nxt[0] - start, called - start, waited - called]
            for i, ((start, called, waited), nxt)
            in enumerate(zip(stamps, stamps[1:]))]
    return [[i] + [round(v, 4) for v in row]
            for i, *row in sorted(rows, key=lambda r: -r[1])[:count]]


def run(run: harness.Run, config: dict, traffic: dict, devices):
    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.parallel_base import parallelize
    from paddle_tpu.jit import TrainStep

    args = run.args
    mesh_axes = traffic.get("mesh")
    mesh = None
    if mesh_axes:
        n = math.prod(mesh_axes.values())
        mesh = mesh_mod.init_mesh(dict(mesh_axes), devices=devices[:n])
    try:
        family = config["family"]
        model, _ = harness.build_model(run, config, traffic["amp"])
        if mesh is not None:
            parallelize(model)
        opt = paddle.optimizer.AdamW(
            parameters=model.parameters(),
            learning_rate=traffic["learning_rate"],
            weight_decay=traffic["weight_decay"])
        step = TrainStep(model, family.loss_fn(), opt)
        batch, seq, ring = traffic["batch"], traffic["seq"], traffic["ring"]
        batches = _make_batches(
            jnp.asarray(seeds.key_words(args.seed, "batches")), ring, batch,
            seq, min(config["token_id_limit"],
                     family.vocab_size(config["model"])))
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
        _reset_to_seed(step, args.seed, config)
        jax.block_until_ready(step.params)
        run.part("reset_to_seed")
        compiles_before = obs.compile_counts()
        run.setup_done()

        # -- the window ------------------------------------------------------
        losses, window_s, paused, trace, stamps, host_gaps = _timed_window(
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
                 slowest_iterations=_slowest_iterations(stamps),
                 host_gaps=host_gaps,
                 programs_in_window=run.programs_in_window())
        run.record.update(
            kind="train", window_s=window_s, steps=n, tokens=tokens,
            attempted=n, batch=batch, seq=seq, config=config["model"],
            family=family,
            failed=sum(not math.isfinite(v) for v in loss_values),
            compiles_before=compiles_before, compiles_after=compiles_after,
            device=device, device_kind=device["kind"],
            end_to_end={"train_tokens_per_s": tokens / window_s})
        if args.trace:
            t_read = time.perf_counter()
            run.record["step_program_bytes"] = _step_program_bytes(
                step, batches[0])
            run.emit(phase="step_program",
                     seconds=round(time.perf_counter() - t_read, 3),
                     bytes=run.record["step_program_bytes"])

        # -- the check: after the window, outside every timed interval -------
        t_check = time.perf_counter()
        verdict = _check(step, model, config, first_batch=batches[0],
                         loss_values=loss_values,
                         rows=traffic.get("check_rows", 2),
                         with_gradients=bool(args.trace))
        run.emit(phase="check", seconds=round(time.perf_counter() - t_check,
                                              3), **verdict)
        run.record.update(correct=bool(verdict["within"]),
                          compared=verdict["compared"])
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


def _run_whole(jitted, *args):
    """One of the check's whole-model programs, compiled for ``args`` and
    run on them: ``(what it returns, its memory analysis)``.  Compiled
    outside the persistent cache: the machine with the chip caps the
    cache's size, and programs of 26 to 85 MB (gpt2-medium's) would evict
    the set-up's."""
    with harness.no_persistent_cache():
        compiled = jitted.lower(*args).compile()
    return (jax.block_until_ready(compiled(*args)),
            harness.program_bytes(compiled))


def _check(step, model, config, first_batch, loss_values, rows,
           with_gradients):
    """System against reference on the seed's weights: the first timed
    step's loss, the eval logits of the batch's first ``rows`` sequences
    (the traffic file's ``check_rows``), every loss finite; in the traced
    run the per-tensor gradients too.

    The window is over and everything it left has been read, so the step's
    state (an f32 master and two moments a parameter) is released before
    the first whole-model program: the check then holds the model object's
    copy and what it makes itself, 10 B a parameter where the step held 14
    (``_gradient_check``), and fits wherever the step did unless the
    reference's temporaries are in the way; the line says where."""
    clock = harness.PartClock(memory=True)
    family, tol = config["family"], check.tolerances(config["family"])
    params_like, _ = _release_state(step)
    clock.part("release_state")
    # the model object still holds the seed's weights in the types they
    # are computed in (TrainStep trains a copy of its own)
    state = model.functional_state()
    ref_forward = family.reference_forward(config["model"])
    # the system's side stays where the system runs (on a mesh, sharded);
    # the reference's side is gathered onto one chip
    sample_sys = first_batch[:rows]
    programs = {}
    sys_logits, programs["system_forward"] = _run_whole(
        check.system_forward_fn(model), state, sample_sys)
    sys_logits = _one_device(sys_logits)
    clock.part("system_forward")
    weights = {k: _one_device(v) for k, v in state.items()}
    first_batch = _one_device(first_batch)
    sample = first_batch[:rows]
    ref_loss = check.reference_loss(ref_forward, family.loss_of_logits,
                                    weights, first_batch, rows)
    loss_rel = abs(loss_values[0] - ref_loss) / abs(ref_loss)
    clock.part("reference_loss")
    errors = check.logits_errors(sys_logits, ref_forward(weights, sample))
    del sys_logits
    clock.part("logits_compare")
    finite = all(math.isfinite(v) for v in loss_values)
    compared = {"loss_rel": [loss_rel, tol["loss_rel"]],
                "logits_rel_rms": [errors["rel_rms"], tol["logits_rel_rms"]]}
    verdict = {
        "kind": "train", "loss_first_step": loss_values[0],
        "loss_reference": ref_loss, "loss_rel_err": loss_rel,
        "logits": errors, "losses_finite": finite, "parts_s": clock.parts,
        "memory": clock.memory, "programs": programs}
    if with_gradients:
        grads = _gradient_check(
            step, params_like, family.reference_loss(config["model"]),
            weights, sample_sys, sample, clock, programs)
        verdict["gradients"] = grads
        compared["grad_rel_worst"] = [grads["worst"], tol["grad_rel"]]
    verdict["compared"] = compared
    verdict["within"] = bool(finite and errors["finite"] and all(
        value < limit for value, limit in compared.values()))
    return verdict


def _gradient_check(step, params_like, ref_loss, weights, sample_sys, sample,
                    clock, programs):
    """Per-tensor gradients of the system's own loss-and-grad computation
    (``TrainStep._grads_core``, the one the compiled step runs) against
    ``jax.grad`` of the reference's loss ``ref_loss``, on the sampled
    sequences, at the seed's weights.  ``params_like`` describes the state
    the step had (``_release_state``): the f32 copy of the seed's weights
    is placed as the step's parameters were.

    At its fullest, while the reference's program runs, the device holds
    the model's copy, the system's gradients and one f32 tree that goes in
    as the weights and comes out as the reference's gradients (the
    argument is donated: the system's side is done with it), beside the
    reference's temporaries."""
    # a copy in every case (``astype`` hands an f32 model's own array
    # back): the reference's program below takes its argument's buffers
    params = {k: _place_like(jnp.array(weights[k], dtype=v.dtype), v)
              for k, v in params_like.items()}
    key = jax.random.key(0)
    (_, _, sys_grads), programs["system_gradients"] = _run_whole(
        jax.jit(step._grads_core), params, step.buffers, key,
        (sample_sys, sample_sys))
    sys_grads = {k: _one_device(v) for k, v in sys_grads.items()}
    clock.part("system_gradients")
    # on one chip these are ``params`` themselves, on a mesh a gathered
    # copy: either way nothing reads them after the reference's program
    ref_params = {k: _one_device(params.pop(k)) for k in sorted(params)}
    ref_grads, programs["reference_gradients"] = _run_whole(
        jax.jit(jax.grad(ref_loss), donate_argnums=0), ref_params, sample)
    del ref_params
    clock.part("reference_gradients")
    out = check.grad_errors(sys_grads, ref_grads)
    clock.part("gradients_compare")
    return out
