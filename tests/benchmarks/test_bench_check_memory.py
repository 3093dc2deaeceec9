"""The training check fits where the step fits (PR 29): before its first
whole-model program ``_check`` releases the step's state, so the check's
persistent bytes are the model object's copy and what it makes itself; the
verdict is number for number the one the check gave before; the compiled
step's own memory analysis is read for ``step_footprint_gb.train``.

The check as it stood before PR 29 is kept here (``parent_check``), as
``test_bench_programs.py`` keeps the build path of before PR 27: the same
test that holds the new check to its bytes shows the old one over them."""
import gc
import json
import math
import time

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from benchmarks.lib import check, harness, seeds, train
from test_bench_run import (REHEARSAL_SPEC, lines_of, mesh_spec_file,
                            rehearse_toy, run_py)

SEED = 2 ** 31 + 29
BATCH, SEQ, ROWS = 2, 128, 2


# -- the check of before PR 29, equation for equation ---------------------------

def parent_check(step, model, config, first_batch, loss_values, rows,
                 with_gradients):
    family, tol = config["family"], check.tolerances(config["family"])
    state = model.functional_state()
    ref_forward = family.reference_forward(config["model"])
    sample_sys = first_batch[:rows]
    with harness.no_persistent_cache():
        sys_logits = jax.block_until_ready(
            check.system_forward_fn(model)(state, sample_sys))
    sys_logits = train._one_device(sys_logits)
    weights = {k: train._one_device(v) for k, v in state.items()}
    first_batch = train._one_device(first_batch)
    sample = first_batch[:rows]
    ref_loss = check.reference_loss(ref_forward, family.loss_of_logits,
                                    weights, first_batch, rows)
    loss_rel = abs(loss_values[0] - ref_loss) / abs(ref_loss)
    errors = check.logits_errors(sys_logits, ref_forward(weights, sample))
    compared = {"loss_rel": [loss_rel, tol["loss_rel"]],
                "logits_rel_rms": [errors["rel_rms"], tol["logits_rel_rms"]]}
    verdict = {"logits": errors}
    if with_gradients:
        grads = parent_gradient_check(
            step, family.reference_loss(config["model"]), weights,
            sample_sys, sample)
        verdict["gradients"] = grads
        compared["grad_rel_worst"] = [grads["worst"], tol["grad_rel"]]
    verdict["compared"] = compared
    return verdict


def parent_gradient_check(step, ref_loss, weights, sample_sys, sample):
    params = {k: train._place_like(weights[k].astype(v.dtype), v)
              for k, v in step.params.items()}
    ref_params = {k: train._one_device(v) for k, v in params.items()}
    key = jax.random.key(0)
    with harness.no_persistent_cache():
        _, _, sys_grads = jax.block_until_ready(jax.jit(step._grads_core)(
            params, step.buffers, key, (sample_sys, sample_sys)))
    sys_grads = {k: train._one_device(v) for k, v in sys_grads.items()}
    with harness.no_persistent_cache():
        ref_grads = jax.block_until_ready(
            jax.jit(jax.grad(ref_loss))(ref_params, sample))
    return check.grad_errors(sys_grads, ref_grads)


# -- a step after a short window, and a probe at each gradient program ----------

def live_bytes():
    gc.collect()
    return harness.live_bytes()


class Probe:
    """Records, at the moment each of the check's two gradient programs is
    traced (which is when it is called: each is compiled for this call),
    whether the step still holds its state and how far the live device
    bytes have grown over ``baseline``."""

    def __init__(self, step, family, baseline, monkeypatch):
        self.step, self.baseline, self.seen = step, baseline, {}
        self._grads_core, reference_loss = (step._grads_core,
                                            family.reference_loss)

        def reference(model):
            loss = reference_loss(model)

            def probed(*args):
                self.note("reference")
                return loss(*args)
            return probed
        monkeypatch.setattr(family, "reference_loss", reference)
        self.arm()

    def arm(self):
        """A new function object a check: JAX traces one only once."""
        def system(*args):
            self.note("system")
            return self._grads_core(*args)
        self.seen.clear()
        self.step._grads_core = system   # an instance attribute of the step

    def note(self, which):
        self.seen[which] = {
            "state_gone": (self.step.params is None
                           and self.step.opt_state is None),
            "grown": live_bytes() - self.baseline}


def build_step(config, amp):
    """(step, model, the seed's weights) as ``train.run`` builds them."""
    from bench_support import QuietRun
    from paddle_tpu.jit import TrainStep
    model, made = harness.build_model(QuietRun(SEED), config, amp)
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-4, weight_decay=0.01)
    return TrainStep(model, config["family"].loss_fn(), opt), model, made


@pytest.fixture
def windowed(tiny_config):
    """(step, model, batches, the losses of two steps from the seed's
    state, live bytes before the model was built)."""
    from paddle_tpu.kernels import flash_attention as fa
    baseline = live_bytes()
    with fa.interpret_scope():
        step, model, _ = build_step(tiny_config, amp=True)
        batches = train._make_batches(
            jnp.asarray(seeds.key_words(SEED, "batches")), 2, BATCH, SEQ,
            500)
        losses = [float(step(x, x).numpy()) for x in batches]
        yield step, model, batches, losses, baseline
    del step, model
    gc.collect()


def test_the_check_releases_the_steps_state_before_its_programs(
        windowed, tiny_config, monkeypatch):
    step, model, batches, losses, baseline = windowed
    family = tiny_config["family"]
    model_bytes = sum(v.nbytes for v in model.functional_state().values())
    f32_tree = 4 * sum(int(v.size) for v in step.params.values())
    allowed = (model_bytes + 3 * f32_tree
               + sum(b.nbytes for b in batches) + 2 * ROWS * SEQ * 4)
    probe = Probe(step, family, baseline, monkeypatch)
    call = dict(first_batch=batches[0], loss_values=losses, rows=ROWS,
                with_gradients=True)

    # the check of before PR 29: the step's 12 B a parameter are still
    # there at both programs, beside the check's own
    before = parent_check(step, model, tiny_config, **call)
    assert set(probe.seen) == {"system", "reference"}
    for seen in probe.seen.values():
        assert not seen["state_gone"]
        assert seen["grown"] > allowed
    assert probe.seen["reference"]["grown"] >= model_bytes + 5 * f32_tree

    probe.arm()
    verdict = train._check(step, model, tiny_config, **call)
    assert set(probe.seen) == {"system", "reference"}
    for which, seen in probe.seen.items():
        assert seen["state_gone"], which
        assert seen["grown"] <= allowed, (which, seen["grown"], allowed)
    assert step.params is None and step.opt_state is None

    # number for number the verdict of before
    assert verdict["compared"] == before["compared"]
    assert verdict["logits"] == before["logits"]
    for key in ("worst", "tensor", "median"):
        assert verdict["gradients"][key] == before["gradients"][key]
    assert verdict["within"] is True
    assert all(math.isfinite(v) for v, _ in verdict["compared"].values())

    # and the line says where the bytes were
    parts = list(verdict["memory"])
    assert parts == list(verdict["parts_s"]) == [
        "release_state", "system_forward", "reference_loss",
        "logits_compare", "system_gradients", "reference_gradients",
        "gradients_compare"]
    released = (verdict["memory"]["release_state"]["live_before"]
                - verdict["memory"]["system_forward"]["live_before"])
    assert released >= 3 * f32_tree          # the master and two moments
    assert set(verdict["programs"]) == {
        "system_forward", "system_gradients", "reference_gradients"}
    for held in verdict["programs"].values():
        assert held["total"] == (
            held["argument"] + held["output"] - held["alias"]
            + held["temp"] + held["generated_code"])
    json.dumps(verdict)                       # it is printed as a line


def test_the_untraced_check_releases_the_state_too(windowed, tiny_config):
    step, model, batches, losses, _ = windowed
    verdict = train._check(step, model, tiny_config, first_batch=batches[0],
                           loss_values=losses, rows=ROWS,
                           with_gradients=False)
    assert step.params is None and step.opt_state is None
    assert "gradients" not in verdict
    assert list(verdict["memory"])[0] == "release_state"
    assert verdict["within"] is True


def test_the_model_keeps_its_weights_when_the_reference_takes_its_argument(
        windowed, tiny_config):
    """The reference's gradient program is given its argument's buffers
    (donated).  For a model kept in float32 ``astype`` would hand the
    model's own arrays to it: the check copies first."""
    from paddle_tpu.kernels import flash_attention as fa
    _, _, batches, _, _ = windowed
    with fa.interpret_scope():
        step, model, made = build_step(tiny_config, amp=False)
        losses = [float(step(batches[0], batches[0]).numpy())]
        verdict = train._check(
            step, model, tiny_config, first_batch=batches[0],
            loss_values=losses, rows=ROWS, with_gradients=True)
    assert verdict["within"] is True
    for name, value in model.functional_state().items():
        assert not value.is_deleted(), name
        assert bool(jnp.all(value == made[name])), name


# -- the verdict through run.py, against the numbers of before PR 29 ------------

#: what the tree of before PR 29 printed for these rehearsals, traced, on
#: the CPU (run in the sandbox while this PR was built)
RECORDED = {
    "train_gpt2m_s1024": {
        "loss_rel": 1.5236799674785748e-07,
        "logits_rel_rms": 4.109581652755878e-07,
        "grad_rel_worst": 6.442517133109504e-07},
    "train_convmix_1row": {
        "loss_rel": 0.0, "logits_rel_rms": 0.0,
        "grad_rel_worst": 3.7103245631442405e-07},
    "train_mesh": {
        "loss_rel": 1.1358814460846109e-07,
        "logits_rel_rms": 6.062447823751427e-07,
        "grad_rel_worst": 8.826102089187771e-07},
}


@pytest.mark.parametrize("workload,seed", [
    ("train_gpt2m_s1024", 5), ("train_convmix_1row", 2 ** 31 + 7),
    ("train_mesh", 3)])
def test_the_traced_verdict_is_the_parents_number_for_number(
        tmp_path, rehearsal_spec, grown_spec_file, workload, seed):
    """The same programs on the same inputs give the same numbers, to the
    last digit, on one chip and on four virtual devices: the release and
    the donated argument change where bytes live, not what is computed."""
    if workload == "train_convmix_1row":
        proc = rehearse_toy(grown_spec_file, seed)
    else:
        spec_file = (mesh_spec_file(tmp_path, rehearsal_spec)
                     if workload == "train_mesh" else REHEARSAL_SPEC)
        proc = run_py("--workload", workload, "--seed", str(seed),
                      "--seconds", "2", "--trace", "1", "--rehearse",
                      "--spec", spec_file)
    lines = lines_of(proc)
    result = lines[-1]
    assert result["correct"] is True
    assert {k: v["value"] for k, v in result["compared"].items()
            } == RECORDED[workload]
    checked = [l for l in lines if l.get("phase") == "check"][0]
    memory = checked["memory"]
    # the state went before the first whole-model program, and the
    # gradients were compared after it
    assert list(memory)[0] == "release_state"
    assert (memory["system_forward"]["live_before"]
            < memory["release_state"]["live_before"])
    assert "grad_rel_worst" in checked["compared"]
    assert checked["parts_s"]["reference_gradients"] >= 0
    held = [l for l in lines if l.get("phase") == "step_program"][0]
    assert held["bytes"]["total"] > 0 and held["seconds"] < 5.0


# -- step_footprint_gb.train ----------------------------------------------------

#: the compiled step of train_gpt2m_s1024 on the chip (three traced runs
#: of PR 29 read the same bytes; PERF.md section 4)
RECORDED_STEP = {"argument": 4_258_587_648, "output": 4_258_460_160,
                 "temp": 10_088_178_688, "alias": 4_258_456_064,
                 "generated_code": 285_645_824}


def test_the_footprint_reader_against_a_recorded_analysis():
    read = harness.layer_reader("step_footprint_gb.train")
    held = dict(RECORDED_STEP)
    held["total"] = (held["argument"] + held["output"] - held["alias"]
                     + held["temp"] + held["generated_code"])
    run = {"kind": "train", "step_program_bytes": held}
    assert read({}, None, run) == 14.632416256
    # nothing to read: no number, never 0
    assert read({}, None, {"kind": "train"}) is None
    assert read({}, None, {"kind": "train",
                           "step_program_bytes": None}) is None
    assert read({}, None, dict(run, rehearsal=True)) is None
    assert read({}, None, dict(run, kind="serve_open")) is None


def test_program_bytes_is_the_compiled_programs_own_analysis():
    ones = jnp.ones((64, 64), jnp.float32)
    compiled = jax.jit(lambda a, b: a @ b + 1.0, donate_argnums=0).lower(
        ones, ones).compile()
    held = harness.program_bytes(compiled)
    analysis = compiled.memory_analysis()
    assert (held["argument"] == analysis.argument_size_in_bytes
            == 2 * 64 * 64 * 4)
    assert held["alias"] == analysis.alias_size_in_bytes
    assert held["output"] == analysis.output_size_in_bytes
    assert held["temp"] == analysis.temp_size_in_bytes
    assert held["total"] == (
        held["argument"] + held["output"] - held["alias"] + held["temp"]
        + held["generated_code"])


def test_the_steps_analysis_is_found_again_without_a_compile(windowed):
    """``_step_program_bytes`` lowers the step for the arguments it ran
    with: JAX finds trace, lowering and executable in its own caches, so
    the step's jit cache does not grow and nothing is compiled."""
    from paddle_tpu import observability as obs
    step, _, batches, _, _ = windowed
    programs = step._step.compile_count
    counts = obs.compile_counts().get("jit.train_step", 0)
    log = harness.CompileLog()
    t0 = time.perf_counter()
    held = train._step_program_bytes(step, batches[0])
    seconds = time.perf_counter() - t0
    assert held["argument"] > 0 and held["alias"] > 0    # the donated state
    assert held["total"] >= held["argument"] + held["temp"]
    assert step._step.compile_count == programs
    assert obs.compile_counts().get("jit.train_step", 0) == counts
    assert log.compile_seconds == 0.0 and seconds < 2.0
