"""The ``nemotron_h`` family at tiny size on the CPU: the program against
``nemotron_h_ref`` for each kind of block alone and for the nine-block
pattern (logits, loss, every gradient; float32 tight, bf16 within the
family's tolerance), the shares of an expert block summing to the whole,
the controls that must read not correct, the arithmetic of the family's
FLOPs and of ``lib/flops_nemotron_h.py`` by hand, and the configuration file
against the catalog's sizes."""
import ast
import inspect
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_support import QuietRun
from benchmarks.families import nemotron_h as family
from benchmarks.lib import check, flops_nemotron_h as shapes, harness, seeds
from benchmarks.reference import nemotron_h_ref as ref

ROOT = harness.ROOT
CELL = "train_nemo3nano_s8192"
CONFIG_FILE = os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.json")
PATTERNS = ["M", "*", "E", "MEMEM*EME"]


def tiny_run(pattern):
    """The configuration file as a rehearsal takes it, with ``pattern``."""
    config = harness.as_run(harness.load_json(CONFIG_FILE), rehearse=True)
    config["model"] = dict(config["model"], hybrid_override_pattern=pattern,
                           head_dim=16, recompute="")
    return config


def ids_of(seed, rows=2, width=40):
    return jnp.asarray(seeds.rng(seed, "ids").integers(
        0, 500, (rows, width)), jnp.int32)


def system_loss_and_grads(model, weights, ids):
    """The training step's own loss-and-gradient computation."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    step = TrainStep(model, family.loss_fn(), paddle.optimizer.AdamW(
        parameters=model.parameters(), learning_rate=1e-4))
    params = {k: jnp.asarray(weights[k], jnp.float32) for k in step.params}
    loss, _, grads = jax.jit(step._grads_core)(
        params, step.buffers, jax.random.key(0), (ids, ids))
    return float(loss), grads


@pytest.mark.parametrize("pattern", PATTERNS)
def test_float32_program_equals_the_reference(pattern):
    """Logits, loss and every gradient: in float32 program and reference are
    one function, whatever the kind of block."""
    config = tiny_run(pattern)
    model, weights = harness.build_model(QuietRun(7), config, amp=False)
    ids = ids_of(7)
    model.eval()
    logits = check.logits_errors(
        check.system_forward_fn(model)(weights, ids),
        family.reference_forward(config["model"])(weights, ids))
    assert logits["finite"] and logits["rel_max"] < 1e-4
    loss, grads = system_loss_and_grads(model, weights, ids)
    ref_loss = family.reference_loss(config["model"])
    trainable = {k: weights[k] for k in grads}
    assert loss == pytest.approx(float(ref_loss(trainable, ids)), rel=1e-5)
    errors = check.grad_errors(grads, jax.jit(jax.grad(ref_loss))(
        trainable, ids))
    assert errors["worst"] < 1e-3, errors


@pytest.mark.parametrize("pattern", PATTERNS)
def test_bf16_program_is_within_the_familys_tolerance(pattern):
    config = tiny_run(pattern)
    tol = check.tolerances(family)
    worst = 0.0
    for seed in (0, 2 ** 31 + 252):
        model, weights = harness.build_model(QuietRun(seed), config,
                                             amp=True)
        model.eval()
        ids = ids_of(seed)
        errors = check.logits_errors(
            check.system_forward_fn(model)(weights, ids),
            family.reference_forward(config["model"])(weights, ids))
        assert errors["finite"]
        worst = max(worst, errors["rel_rms"])
    assert worst < tol["logits_rel_rms"]


@pytest.mark.parametrize("control,pattern", [
    ("no_experts", "MEMEM*EME"), ("no_carried_state", "MEMEM*EME"),
    ("no_carried_state", "MM*")])
def test_a_broken_reference_fails_a_tolerance(monkeypatch, control, pattern):
    """Without its expert blocks the reference's logits are far off; a scan
    that forgets its state between chunks moves the logits of a randomly
    initialised model little (a mixer writes a small step into the residual
    stream, and only its slowly decaying heads reach across a chunk), but
    the gradients of the mixer's own tensors by far more than the limit:
    the control is decided by the traced run's gradient comparison."""
    config = tiny_run(pattern)
    _, weights = harness.build_model(QuietRun(3), config, amp=False)
    ids = ids_of(3)
    tol = check.tolerances(family)
    whole = family.reference_forward(config["model"])(weights, ids)
    whole_grads = jax.jit(jax.grad(family.reference_loss(config["model"])))(
        weights, ids)
    monkeypatch.setenv("NEMOTRON_H_REFERENCE_CONTROL", control)
    broken = family.reference_forward(config["model"])(weights, ids)
    broken_grads = jax.jit(jax.grad(family.reference_loss(
        config["model"])))(weights, ids)
    logits = check.logits_errors(broken, whole)["rel_rms"]
    grads = check.grad_errors(broken_grads, whole_grads)
    assert grads["worst"] > 2 * tol["grad_rel"], grads
    if control == "no_experts":
        assert logits > 2 * tol["logits_rel_rms"]
    else:
        assert ".mixer." in grads["tensor"]
    monkeypatch.setenv("NEMOTRON_H_REFERENCE_CONTROL", "nonesuch")
    with pytest.raises(SystemExit):
        family.reference_forward(config["model"])


def test_the_reference_at_bf16_reads_far_from_itself():
    """The control of ``tools/nemotron_h_controls.py``: the reference with
    every tensor and every sum in bf16 is no float32 reference."""
    config = tiny_run("MEMEM*EME")
    _, weights = harness.build_model(QuietRun(5), config, amp=False)
    ids = ids_of(5)
    exact = ref.forward(weights, ids, config["model"])
    rounded = ref.forward(weights, ids, config["model"], dtype=jnp.bfloat16)
    assert check.logits_errors(rounded, exact)["rel_rms"] > 0.005


@pytest.mark.parametrize("pattern", ["M", "*", "E", "MM"])
def test_a_kind_of_block_alone_is_nearer_than_its_bf16_reference(pattern):
    """The float32 islands, where no router's choice is in the way: one
    kind of block alone, the bf16 program (float32 decays, carried state,
    norms, sums) reads well under the reference rounded to bf16 everywhere
    but its router, on every seed (0.61-0.70 of it as measured here; the
    nine blocks on the chip read 0.75 of it, PERF.md section 6)."""
    config = tiny_run(pattern)
    for seed in (0, 5, 2 ** 31 + 252):
        model, weights = harness.build_model(QuietRun(seed), config,
                                             amp=True)
        model.eval()
        ids = ids_of(seed)
        exact = family.reference_forward(config["model"])(weights, ids)
        program = check.logits_errors(
            check.system_forward_fn(model)(weights, ids), exact)["rel_rms"]
        rounded = check.logits_errors(ref.forward(
            weights, ids, config["model"], jnp.bfloat16,
            router_dtype=jnp.float32), exact)["rel_rms"]
        assert program < 0.8 * rounded, (seed, program, rounded)


def test_a_block_given_a_choice_of_experts_uses_it():
    """``chosen``: the probe's seam (the reference told what another run
    chose).  Its own choice given back changes nothing; another choice
    does; the weights stay this run's scores of the chosen."""
    config = tiny_run("E")
    model_dict = config["model"]
    _, weights = harness.build_model(QuietRun(9), config, amp=False)
    w = ref.layer_weights(weights, 0, ref.EXPERTS)
    x = ref.embed(weights["backbone.embeddings.weight"], ids_of(9))
    own = ref.choice(x, w, model_dict)
    k = model_dict["num_experts_per_tok"]
    assert own.shape == x.shape[:2] + (k,)
    free = ref.block(x, w, ref.EXPERTS, model_dict)
    np.testing.assert_array_equal(
        ref.block(x, w, ref.EXPERTS, model_dict, chosen=own), free)
    held = jnp.asarray(model_dict["held_experts"][:k], own.dtype)
    other = ref.block(x, w, ref.EXPERTS, model_dict,
                      chosen=jnp.broadcast_to(held, own.shape))
    assert float(jnp.max(jnp.abs(other - free))) > 0
    # a float32 router under bf16 blocks chooses as float32 scores of the
    # rounded input do: nearer the float32 choice than a bf16 router's
    flips = lambda a: int((a[..., :, None] != own[..., None, :]).all(-1).sum())
    assert flips(ref.choice(x, w, model_dict, jnp.bfloat16, jnp.float32)) \
        <= flips(ref.choice(x, w, model_dict, jnp.bfloat16))


# -- the flash kernels' time in a step with other kernels ----------------------

def test_flash_time_is_the_mosaic_calls_under_the_attention_scope(
        monkeypatch):
    """``flash_attn_ms.train`` on PR 23's recorded chip trace: with every
    Mosaic call under ``attn`` it is ``flash_ms.train``; calls the program
    files elsewhere (a grouped product) are left out; no index, no number."""
    import paddle_tpu.observability.scopes as program_scopes
    from benchmarks.lib import scopes as S, trace as T
    with open(os.path.join(ROOT, "tests", "benchmarks",
                           "recorded_trace.json")) as f:
        trace = json.load(f)
    kernels = sorted({S.instruction_name(text) for text, _, _ in
                      trace["devices"]["/device:TPU:0"]["ops"]
                      if T.MOSAIC_MARK in text})
    assert len(kernels) >= 2
    read = harness.layer_reader("flash_attn_ms.train")
    record = {"kind": "train"}
    every = T.mosaic_ms_per_module(trace, "step_fn")
    monkeypatch.setattr(program_scopes, "index", lambda: {
        "jit_step_fn": {name: "attn" for name in kernels}})
    assert read({}, trace, record) == pytest.approx(every)
    monkeypatch.setattr(program_scopes, "index", lambda: {
        "jit_step_fn": {name: "attn" if i % 2 else "moe_experts"
                        for i, name in enumerate(kernels)}})
    assert 0 < read({}, trace, record) < every
    monkeypatch.setattr(program_scopes, "index", lambda: {
        "jit_step_fn": {name: "moe_experts" for name in kernels}})
    assert read({}, trace, record) is None
    monkeypatch.setattr(program_scopes, "index", lambda: {})
    assert read({}, trace, record) is None
    assert read({}, None, record) is None
    assert read({}, trace, {"kind": "serve_open"}) is None
    roofline = harness.layer_reader("flash_attn_roofline_pct.train")
    assert roofline({}, trace, dict(record, rehearsal=True)) is None
    assert roofline({}, None, record) is None


@pytest.mark.parametrize("seed", [0, 1])
def test_the_shares_sum_to_the_whole(seed):
    """An expert block cut sixteen ways: the routed parts of the sixteen
    shares, with the shared expert counted once, equal the uncut reference's
    block, and the program's layer gives each share."""
    from paddle_tpu.nn.layer.experts import RoutedExperts
    import paddle_tpu as paddle
    rng = np.random.default_rng(seed)
    hidden, width, experts, k, shares = 32, 24, 32, 6, 16
    w = {"norm.weight": jnp.ones((hidden,)),
         "mixer.gate.weight": jnp.asarray(
             rng.normal(0, 1.0, (hidden, experts)), jnp.float32),
         ref.BIAS: jnp.asarray(rng.normal(0, 0.1, (experts,)), jnp.float32),
         "mixer.experts.up_proj": jnp.asarray(
             rng.normal(0, 0.3, (experts, hidden, width)), jnp.float32),
         "mixer.experts.down_proj": jnp.asarray(
             rng.normal(0, 0.3, (experts, width, hidden)), jnp.float32),
         "mixer.shared_experts.up_proj.weight": jnp.asarray(
             rng.normal(0, 0.3, (hidden, 40)), jnp.float32),
         "mixer.shared_experts.down_proj.weight": jnp.asarray(
             rng.normal(0, 0.3, (40, hidden)), jnp.float32)}
    u = jnp.asarray(rng.normal(0, 1.0, (2, 24, hidden)), jnp.float32)
    whole = ref.experts(u, w, list(range(experts)), k, 2.5)
    shared = ref.relu2_mlp(u, w["mixer.shared_experts.up_proj.weight"],
                           w["mixer.shared_experts.down_proj.weight"])
    total = shared
    per = experts // shares
    for share in range(shares):
        held = list(range(share * per, (share + 1) * per))
        cut = dict(w, **{
            "mixer.experts.up_proj": w["mixer.experts.up_proj"][
                share * per:(share + 1) * per],
            "mixer.experts.down_proj": w["mixer.experts.down_proj"][
                share * per:(share + 1) * per]})
        part = ref.experts(u, cut, held, k, 2.5, with_shared=False)
        total = total + part
        # the program's layer, told it holds this share
        layer = RoutedExperts(hidden, width, experts, k, held=held,
                              shared_intermediate_size=40,
                              routed_scaling_factor=2.5)
        layer.gate.weight._array = w["mixer.gate.weight"]
        layer.gate.e_score_correction_bias._array = w[ref.BIAS]
        layer.experts.up_proj._array = cut["mixer.experts.up_proj"]
        layer.experts.down_proj._array = cut["mixer.experts.down_proj"]
        layer.shared_experts.up_proj.weight._array = w[
            "mixer.shared_experts.up_proj.weight"]
        layer.shared_experts.down_proj.weight._array = w[
            "mixer.shared_experts.down_proj.weight"]
        got = layer(paddle.Tensor(u))._array
        np.testing.assert_allclose(got, part + shared, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-4)


# -- the cell through run.py ---------------------------------------------------

def rehearse(control=None, seed=2 ** 31 + 11):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("NEMOTRON_H_REFERENCE_CONTROL", None)
    if control:
        env["NEMOTRON_H_REFERENCE_CONTROL"] = control
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "1",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]


@pytest.fixture(scope="module")
def rehearsed():
    return rehearse()


def test_the_new_cell_rehearses_correct(rehearsed):
    last = rehearsed[-1]
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["failed"] == 0 and last["metrics"] == {}
    assert set(last["compared"]) == {"loss_rel", "logits_rel_rms",
                                     "grad_rel_worst"}
    # what a CPU run can read of the new metrics: the counter's
    assert "moe_padded_rows_pct.train" in last["rehearsed_metric_names"]


def test_the_rehearsal_compiles_nothing_in_its_window(rehearsed):
    window = [l for l in rehearsed if l.get("phase") == "window"][0]
    assert window["programs_in_window"]["cache_misses"] == 0
    assert window["steps"] >= 2


@pytest.mark.parametrize("control", ["no_experts", "no_carried_state"])
def test_a_reference_without_a_layer_reads_not_correct(control):
    """The controls of the new family, as the toy's: its reference with the
    expert blocks dropped, or with a scan that forgets its state between
    chunks, must fail what the whole reference passes."""
    last = rehearse(control)[-1]
    assert last["correct"] is False
    compared = last["compared"]
    assert compared["grad_rel_worst"]["value"] > 2 * compared[
        "grad_rel_worst"]["limit"]
    if control == "no_experts":
        assert compared["logits_rel_rms"]["value"] > 2 * compared[
            "logits_rel_rms"]["limit"]


# -- arithmetic by hand --------------------------------------------------------

@pytest.fixture(scope="module")
def as_run():
    return harness.as_run(harness.load_json(CONFIG_FILE), rehearse=False)


def test_train_flops_per_token_by_hand(as_run):
    model = as_run["model"]
    mamba = 2688 * (4096 + 6144 + 64) + 4096 * 2688
    attention = 2688 * (32 + 2 + 2) * 128 + 4096 * 2688
    experts = (2688 * 128 + 2 * 2688 * 3712
               + 6 * 8 / 128 * 2 * 2688 * 1856)
    assert (mamba, attention, experts) == (38_707_200, 23_396_352,
                                           24_041_472.0)
    matmul = 4 * mamba + attention + 4 * experts + 2688 * 16384
    assert matmul == 318_431_232
    flash = 6 * 8192 * 32 * 128
    # the scan: a chunk of 128 tokens, 8 groups of state 128, 64 heads of 64
    chunk = 8 * 128 * 128 * 128 + 64 * 128 * 128 * 64 + 4 * 64 * 128 * 64 * 128
    scan = 4 * 3 * chunk / 128
    want = 6 * matmul + flash + scan
    assert family.train_flops_per_token(model, 8192) == pytest.approx(want)
    assert want == pytest.approx(2.1449e9, rel=1e-4)
    # no term but attention's grows with the row
    assert (family.train_flops_per_token(model, 4096)
            == pytest.approx(want - flash / 2))


def test_scan_flops_and_bytes_by_hand(as_run):
    model = as_run["model"]
    ops = shapes.scan_flops(1, 8192, model)
    assert ops["fwd"] == 64 * (16_777_216 + 67_108_864 + 268_435_456)
    assert ops["bwd"] == 2 * ops["fwd"] and ops["total"] == 3 * ops["fwd"]
    moved = shapes.scan_bytes(1, 8192, model)
    x, bc, dt = 8192 * 4096 * 2, 2 * 8192 * 1024 * 2, 8192 * 64 * 4
    assert moved == {"fwd": 2 * x + bc + dt, "bwd": 3 * x + 2 * (bc + dt)}
    peak = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    # by these least bytes the scan is bound by bandwidth both ways: 0.207
    # and 0.333 ms a layer against 0.114 and 0.229 ms of compute
    assert moved["fwd"] / 819e9 > ops["fwd"] / 197e12
    assert moved["bwd"] / 819e9 > ops["bwd"] / 197e12
    assert shapes.scan_least_seconds(4, 1, 8192, model, peak) == \
        pytest.approx(4 * (moved["fwd"] + moved["bwd"]) / 819e9)


def test_grouped_flops_and_bytes_by_hand(as_run):
    model = as_run["model"]
    assert shapes.expected_held_rows(1, 8192, model) == 3072
    ops = shapes.grouped_flops(3072, model)
    one = 2 * 3072 * 2688 * 1856
    assert ops == {"fwd": 2 * one, "bwd": 4 * one, "total": 6 * one}
    assert shapes.grouped_bytes(3072, model) == (
        3072 * (2688 + 1856) + 8 * 2688 * 1856) * 2
    peak = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    # 384 rows an expert: 0.156 ms of compute a product against 0.132 ms
    # for its rows and its weights read once
    by_bytes = shapes.grouped_bytes(3072, model) / 819e9
    assert one / 197e12 > by_bytes > 0.8 * one / 197e12
    assert shapes.grouped_least_seconds(4, 1, 8192, model, peak) == \
        pytest.approx(4 * 6 * one / 197e12)


# -- the configuration file ----------------------------------------------------

def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows
            if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"][0]


def test_the_file_holds_every_key_of_the_source():
    config = harness.load_json(CONFIG_FILE)
    row = catalog_row()
    assert config["source"] == row["source_url"]
    assert config["published"] == row["config"]
    differ = {k for k, v in row["config"].items() if config[k] != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    entry = [c for c in harness.benchmark_spec()["configs"]
             if c["name"] == config["name"]][0]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]


def test_the_cut_is_the_patterns_start_and_the_counts_add_up(as_run):
    config, model = as_run, as_run["model"]
    published = config["published"]
    assert published["hybrid_override_pattern"].startswith(
        model["hybrid_override_pattern"])
    assert len(model["hybrid_override_pattern"]) == config[
        "num_hidden_layers"] == 9
    assert model["held_experts"] == list(range(8))
    assert model["router_width"] == published["n_routed_experts"] == 128
    assert config["token_id_limit"] == model["vocab_size"] == \
        published["vocab_size"] // 8
    # parameters as run: ISSUE 33's count
    from benchmarks.lib import weights as weights_mod
    import paddle_tpu  # noqa: F401
    shapes_of = jax.eval_shape(
        lambda: family.build_model(model).functional_state())
    assert sum(int(np.prod(v.shape)) for v in shapes_of.values()) == \
        666_962_944
    spec = weights_mod.leaf_spec(shapes_of, family, model)
    rules = {name: rule for name, _, _, rule in spec}
    assert rules["backbone.layers.0.mixer.out_proj.weight"] == (
        "normal", 0.02 / 3)
    assert rules["backbone.layers.1.mixer.experts.down_proj"] == (
        "normal", 0.02 / 3)
    assert rules["backbone.layers.0.mixer.A_log"][0] == "uniform"
    assert rules["backbone.layers.0.mixer.D"] == ("constant", 1.0)


def test_the_reference_imports_nothing_from_the_program():
    tree = ast.parse(inspect.getsource(ref))
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
              for a in n.names]
    assert not any(n.startswith(("paddle", "benchmarks")) for n in names)


def test_the_new_cell_joins_what_the_issue_lists_and_no_flash_list():
    spec = harness.benchmark_spec()
    reports = set(harness.metric_names(spec, "per_layer", CELL))
    assert {"ssm_ms.train", "ssm_scan_roofline_pct.train", "moe_ms.train",
            "moe_experts_roofline_pct.train", "moe_padded_rows_pct.train",
            "train_step_ms", "train_mfu_pct", "scope_coverage_pct.train",
            "compiles_in_window"} <= reports
    # the grouped products are Mosaic calls too: ``flash_ms.train`` would
    # count them as flash time
    assert not {"flash_ms.train", "flash_roofline_pct.train", "mlp_ms.train",
                "flash_packed_pct.train"} & reports
    for name in ("setup_s", "compiles_in_window"):
        entry = [m for m in spec["end_to_end"] + spec["per_layer"]
                 if m["name"] == name][0]
        assert "workloads" not in entry
    assert len({m["layer"] for m in spec["per_layer"]}) <= 12
