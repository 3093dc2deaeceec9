"""The ``qwen3_next`` family at tiny size on the CPU: the program against
``qwen3_next_ref`` for each kind of mixer alone and for the period of four
layers (logits, loss, every gradient; float32 tight, bf16 within the
family's tolerance), the sixteen shares of an expert layer summing to the
whole, the controls that must read not correct, the cell's rehearsal through
``run.py``, the arithmetic of the family's FLOPs and of
``lib/flops_qwen3_next.py`` by hand, and the configuration file against the
catalog's row."""
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
from benchmarks.families import qwen3_next as family
from benchmarks.lib import check, flops_qwen3_next as shapes, harness, seeds
from benchmarks.reference import qwen3_next_ref as ref

ROOT = harness.ROOT
CELL = "train_qwen3next_s16384"
CONFIG_FILE = os.path.join(ROOT, "benchmarks", "configs",
                           "qwen3-next-80b-a3b.json")
L, A = ref.LINEAR, ref.FULL
KINDS = [pytest.param((L,), id="linear"), pytest.param((A,), id="full"),
         pytest.param((L, L, L, A), id="period")]


def tiny_run(kinds):
    """The configuration file as a rehearsal takes it, with these layers."""
    config = harness.as_run(harness.load_json(CONFIG_FILE), rehearse=True)
    config["model"] = dict(config["model"], layer_types=list(kinds),
                           num_hidden_layers=len(kinds), head_dim=16,
                           recompute=[])
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


@pytest.mark.parametrize("kinds", KINDS)
def test_float32_program_equals_the_reference(kinds):
    """Logits, loss and every gradient: in float32 program (the chunked
    rule, the sorted experts) and reference (a token at a time, dense
    experts) are one function."""
    config = tiny_run(kinds)
    model, weights = harness.build_model(QuietRun(7), config, amp=False)
    ids = ids_of(7)
    model.eval()
    logits = check.logits_errors(
        check.system_forward_fn(model)(weights, ids),
        family.reference_forward(config["model"])(weights, ids))
    assert logits["finite"] and logits["rel_max"] < 1e-4
    loss, grads = system_loss_and_grads(model, weights, ids)
    assert set(grads) == set(weights)          # no buffer, every leaf trains
    ref_loss = family.reference_loss(config["model"])
    assert loss == pytest.approx(float(ref_loss(weights, ids)), rel=1e-5)
    errors = check.grad_errors(grads, jax.jit(jax.grad(ref_loss))(
        weights, ids))
    assert errors["worst"] < 1e-3, errors


@pytest.mark.parametrize("kinds", KINDS)
def test_bf16_program_is_within_the_familys_tolerance(kinds):
    config = tiny_run(kinds)
    tol = check.tolerances(family)
    assert tol["logits_rel_rms"] == family.TOLERANCES["logits_rel_rms"]
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


@pytest.mark.parametrize("control", ["no_experts", "no_carried_state",
                                     "no_correction"])
def test_a_broken_reference_fails_a_tolerance(monkeypatch, control):
    """Each control moves a gradient by far more than the limit: without
    its expert layers the reference has no gradient for their tensors at
    all; a delta rule that forgets its state between blocks of the row, or
    that drops ``S^T k_t``, moves the gradients of the mixer's own
    tensors.  (The logits of a tiny, randomly initialised model move
    little: the traced run's gradient comparison decides, as in
    ``nemotron_h``; on the chip the logits fail too, PERF.md section 6.)"""
    config = tiny_run((L, L, L, A))
    _, weights = harness.build_model(QuietRun(3), config, amp=False)
    ids = ids_of(3, width=300)      # the reference's blocks are 128 tokens
    tol = check.tolerances(family)
    whole = family.reference_forward(config["model"])(weights, ids)
    whole_grads = jax.jit(jax.grad(family.reference_loss(config["model"])))(
        weights, ids)
    monkeypatch.setenv("QWEN3_NEXT_REFERENCE_CONTROL", control)
    broken = family.reference_forward(config["model"])(weights, ids)
    broken_grads = jax.jit(jax.grad(family.reference_loss(
        config["model"])))(weights, ids)
    logits = check.logits_errors(broken, whole)["rel_rms"]
    grads = check.grad_errors(broken_grads, whole_grads)
    assert grads["worst"] > 2 * tol["grad_rel"], grads
    assert logits > 0.01
    assert (".mlp." if control == "no_experts" else ".linear_attn.") \
        in grads["tensor"]
    monkeypatch.setenv("QWEN3_NEXT_REFERENCE_CONTROL", "nonesuch")
    with pytest.raises(SystemExit):
        family.reference_forward(config["model"])


def test_the_reference_at_bf16_reads_far_from_itself():
    """The control of ``tools/qwen3_next_controls.py``: the reference with
    every tensor and every sum in bf16 is no float32 reference."""
    config = tiny_run((L, L, L, A))
    _, weights = harness.build_model(QuietRun(5), config, amp=False)
    ids = ids_of(5)
    exact = ref.forward(weights, ids, config["model"])
    rounded = ref.forward(weights, ids, config["model"], dtype=jnp.bfloat16)
    assert check.logits_errors(rounded, exact)["rel_rms"] > 0.005


def test_a_layer_given_a_choice_of_experts_uses_it():
    """``chosen``: the probe's seam (the reference told what another run
    chose).  Its own choice given back changes nothing; another does."""
    config = tiny_run((L,))
    model_dict = config["model"]
    _, weights = harness.build_model(QuietRun(9), config, amp=False)
    w = ref.layer_weights(weights, 0, L)
    x = ref.embed(weights["model.embed_tokens.weight"], ids_of(9))
    own = ref.choice(x, w, L, model_dict)
    k = model_dict["num_experts_per_tok"]
    assert own.shape == x.shape[:2] + (k,)
    free = ref.layer(x, w, L, model_dict)
    np.testing.assert_array_equal(
        ref.layer(x, w, L, model_dict, chosen=own), free)
    other = ref.layer(x, w, L, model_dict, chosen=(own + 1) % 16)
    assert float(jnp.max(jnp.abs(other - free))) > 0


@pytest.mark.parametrize("seed", [0, 1])
def test_the_sixteen_shares_sum_to_the_whole(seed):
    """An expert layer cut sixteen ways: the routed parts of the sixteen
    shares, with the gated shared expert counted once, equal the uncut
    reference's layer, and the program's layer gives each share."""
    from paddle_tpu.nn.layer.experts import RoutedExperts
    import paddle_tpu as paddle
    rng = np.random.default_rng(seed)
    hidden, width, experts, k, shares = 32, 24, 32, 10, 16
    arr = lambda scale, *shape: jnp.asarray(rng.normal(0, scale, shape),
                                            jnp.float32)
    w = {"mlp.gate.weight": arr(1.0, hidden, experts),
         "mlp.experts.gate_proj": arr(0.3, experts, hidden, width),
         "mlp.experts.up_proj": arr(0.3, experts, hidden, width),
         "mlp.experts.down_proj": arr(0.3, experts, width, hidden),
         "mlp.shared_experts.gate_proj.weight": arr(0.3, hidden, 40),
         "mlp.shared_experts.up_proj.weight": arr(0.3, hidden, 40),
         "mlp.shared_experts.down_proj.weight": arr(0.3, 40, hidden),
         "mlp.shared_gate": arr(0.3, hidden)}
    stacked = ("mlp.experts.gate_proj", "mlp.experts.up_proj",
               "mlp.experts.down_proj")
    u = arr(1.0, 2, 24, hidden)
    whole = ref.experts(u, w, list(range(experts)), k)
    shared = ref.shared_expert(u, w)
    total = shared
    per = experts // shares
    for share in range(shares):
        held = list(range(share * per, (share + 1) * per))
        cut = dict(w, **{name: w[name][share * per:(share + 1) * per]
                         for name in stacked})
        part = ref.experts(u, cut, held, k, with_shared=False)
        total = total + part
        # the program's layer, told it holds this share
        layer = RoutedExperts(hidden, width, experts, k, held=held,
                              shared_intermediate_size=40, router="softmax",
                              expert="gated", shared_gate=True)
        layer.gate.weight._array = w["mlp.gate.weight"]
        layer.shared_gate._array = w["mlp.shared_gate"]
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(layer.experts, name)._array = cut["mlp.experts." + name]
            getattr(layer.shared_experts, name).weight._array = w[
                "mlp.shared_experts.%s.weight" % name]
        got = layer(paddle.Tensor(u))._array
        np.testing.assert_allclose(got, part + shared, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-4)


# -- the cell through run.py ---------------------------------------------------

def rehearse(control=None, seed=2 ** 31 + 11):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("QWEN3_NEXT_REFERENCE_CONTROL", None)
    if control:
        env["QWEN3_NEXT_REFERENCE_CONTROL"] = control
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
    # what a CPU run can read of the cell's metrics: the counters'
    assert {"moe_padded_rows_pct.train", "flash_dead_score_pct.train",
            "flash_bwd_resident_pct.train"} <= set(
        last["rehearsed_metric_names"])


def test_the_rehearsal_compiles_nothing_in_its_window(rehearsed):
    window = [l for l in rehearsed if l.get("phase") == "window"][0]
    assert window["programs_in_window"]["cache_misses"] == 0
    assert window["steps"] >= 2


def test_a_reference_without_its_correction_reads_not_correct():
    """One control through ``run.py`` (the two others run in this file's
    direct comparison): the delta rule without ``S^T k_t``."""
    last = rehearse("no_correction")[-1]
    assert last["correct"] is False
    compared = last["compared"]
    assert compared["grad_rel_worst"]["value"] > 2 * compared[
        "grad_rel_worst"]["limit"]


# -- arithmetic by hand --------------------------------------------------------

@pytest.fixture(scope="module")
def as_run():
    return harness.as_run(harness.load_json(CONFIG_FILE), rehearse=False)


def test_train_flops_per_token_by_hand(as_run):
    model = as_run["model"]
    linear = 2048 * (12288 + 64) + 4096 * 2048
    full = 2048 * (8192 + 512 + 512) + 4096 * 2048
    experts = (2048 * 512 + 3 * 2048 * 512 + 2048
               + 10 * 32 / 512 * 3 * 2048 * 512)
    assert (linear, full, experts) == (33_685_504, 27_262_976, 6_162_432.0)
    matmul = 3 * linear + full + 4 * experts + 2048 * 19072
    assert matmul == 192_028_672
    flash = 6 * 16384 * 16 * 256
    # the delta rule: a chunk of 64 tokens, 16 key and 32 value heads of 128
    chunk = (2 * 16 * 2 * 64 * 64 * 128 + 32 * 2 * 64 * 64 * 384
             + 32 * 3 * 2 * 64 * 128 * 128 + 32 * 2 * 64 ** 3 / 3)
    assert chunk == pytest.approx(341_136_725.3)
    rule = 3 * 3 * chunk / 64
    want = 6 * matmul + flash + rule
    assert family.train_flops_per_token(model, 16384) == pytest.approx(want)
    assert want == pytest.approx(1.6028e9, rel=1e-4)
    # no term but attention's grows with the row
    assert (family.train_flops_per_token(model, 8192)
            == pytest.approx(want - flash / 2))
    assert family.flash_calls(model) == [
        {"layers": 1, "heads": 16, "head_dim": 256}]


def test_delta_rule_flops_and_bytes_by_hand(as_run):
    model = as_run["model"]
    ops = shapes.delta_rule_flops(1, 16384, model)
    assert ops["fwd"] == pytest.approx(256 * 341_136_725.33)
    assert ops["bwd"] == 2 * ops["fwd"] and ops["total"] == 3 * ops["fwd"]
    moved = shapes.delta_rule_bytes(1, 16384, model)
    qk, v, gates = 2 * 16384 * 2048 * 2, 16384 * 4096 * 2, 2 * 16384 * 32 * 4
    assert moved == {"fwd": qk + 2 * v + gates,
                     "bwd": 2 * (qk + v + gates) + v}
    peak = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    # by these least bytes the rule is bound by bandwidth both ways: 0.496
    # and 0.746 ms a layer against 0.443 and 0.887 ms of compute... the
    # backward by compute
    assert moved["fwd"] / 819e9 > ops["fwd"] / 197e12
    assert moved["bwd"] / 819e9 < ops["bwd"] / 197e12
    assert shapes.delta_rule_least_seconds(3, 1, 16384, model, peak) == \
        pytest.approx(3 * (moved["fwd"] / 819e9 + ops["bwd"] / 197e12))


def test_gated_grouped_flops_and_bytes_by_hand(as_run):
    model = as_run["model"]
    assert shapes.expected_held_rows(1, 16384, model) == 10240
    ops = shapes.gated_grouped_flops(10240, model)
    one = 2 * 10240 * 2048 * 512
    assert ops == {"fwd": 3 * one, "bwd": 6 * one, "total": 9 * one}
    assert shapes.gated_grouped_bytes(10240, model) == (
        10240 * (2048 + 512) + 32 * 2048 * 512) * 2
    peak = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    # 320 rows an expert: 0.109 ms of compute a product against 0.146 ms
    # for its rows and its weights read once: bound by bandwidth
    by_bytes = shapes.gated_grouped_bytes(10240, model) / 819e9
    assert by_bytes > one / 197e12 > 0.7 * by_bytes
    assert shapes.gated_grouped_least_seconds(4, 1, 16384, model, peak) == \
        pytest.approx(4 * 9 * by_bytes)


# -- the configuration file ----------------------------------------------------

def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows if r["name"] == "Qwen3-Next-80B-A3B-Instruct"][0]


def test_the_file_holds_every_key_of_the_source():
    config = harness.load_json(CONFIG_FILE)
    row = catalog_row()
    assert config["source"] == row["source_url"]
    assert config["published"] == row["config"]
    differ = {k for k, v in row["config"].items() if config[k] != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    entry = [c for c in harness.benchmark_spec()["configs"]
             if c["name"] == config["name"]][0]
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == os.path.relpath(CONFIG_FILE, ROOT)


def test_no_width_differs_from_the_published():
    config = harness.load_json(CONFIG_FILE)
    differ = {name.replace("top_level.", "")
              for name, run, published in family.width_pairs(config)
              if run != published}
    assert differ == set(config["reduced"])
    names = {name for name, _, _ in family.width_pairs(config)}
    assert {"head_dim", "linear_key_head_dim", "moe_intermediate_size",
            "num_experts_per_tok", "router_width"} <= names


def test_the_cut_is_the_first_period_and_the_counts_add_up(as_run):
    config, model = as_run, as_run["model"]
    published = config["published"]
    assert ref.layer_types(model) == (L, L, L, A)
    assert model["num_hidden_layers"] == config["num_hidden_layers"] == \
        published["full_attention_interval"] == 4
    assert model["held_experts"] == list(range(32))
    assert model["router_width"] == published["num_experts"] == 512
    assert config["token_id_limit"] == published["vocab_size"] // 8 == 18992
    assert model["vocab_size"] == 19072 == (
        config["assumed_sizes"]["padded_vocabulary"] // 8)
    assert model["vocab_size"] % 128 == 0
    # parameters as run: ISSUE 35's count
    from benchmarks.lib import weights as weights_mod
    import paddle_tpu  # noqa: F401
    shapes_of = jax.eval_shape(
        lambda: family.build_model(model).functional_state())
    count = lambda part: sum(int(np.prod(v.shape))
                             for k, v in shapes_of.items() if part in k)
    assert count("layers.0.linear_attn.") == 33_718_464
    assert count("layers.3.self_attn.") == 27_263_488
    assert count("layers.0.mlp.") == 104_859_648
    assert count("") == 625_994_816
    spec = weights_mod.leaf_spec(shapes_of, family, model)
    rules = {name: rule for name, _, _, rule in spec}
    assert rules["model.layers.0.linear_attn.out_proj.weight"] == (
        "normal", 0.01)
    assert rules["model.layers.3.self_attn.o_proj.weight"] == (
        "normal", 0.01)
    assert rules["model.layers.1.mlp.experts.down_proj"] == ("normal", 0.01)
    assert rules["model.layers.1.mlp.experts.gate_proj"] == ("normal", 0.02)
    assert rules["model.layers.1.mlp.shared_gate"] == ("normal", 0.02)
    assert rules["model.layers.0.linear_attn.A_log"][0] == "uniform"
    assert rules["model.layers.0.linear_attn.conv1d_weight"] == (
        "uniform", (-0.5, 0.5))
    assert rules["model.layers.0.linear_attn.norm_weight"] == (
        "constant", 1.0)
    for name in ("model.layers.0.input_layernorm.weight",
                 "model.layers.2.post_attention_layernorm.weight",
                 "model.layers.3.self_attn.q_norm.weight",
                 "model.layers.3.self_attn.k_norm.weight",
                 "model.norm.weight"):
        assert rules[name] == ("constant", 0.0), name


def test_the_reference_imports_nothing_from_the_program():
    tree = ast.parse(inspect.getsource(ref))
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
              for a in n.names]
    assert not any(n.startswith(("paddle", "benchmarks")) for n in names)


def test_the_entries_before_this_pr_stand():
    """What PR 35 found is a prefix of what it leaves: the two earlier
    configurations and cells as they were, the last per-layer entry before
    it (PR 34's) untouched, its own three appended after."""
    spec = harness.benchmark_spec()
    assert [c["name"] for c in spec["configs"]] == [
        "gpt2-medium", "nemotron-3-nano-30b-a3b", "qwen3-next-80b-a3b"]
    assert [w["name"] for w in spec["workloads"]] == [
        "train_gpt2m_s1024", "train_nemo3nano_s8192", CELL]
    names = [m["name"] for m in spec["per_layer"]]
    assert names[-3:] == ["linear_attn_ms.train",
                          "linear_attn_scan_roofline_pct.train",
                          "moe_gated_experts_roofline_pct.train"]
    assert spec["per_layer"][-4] == {
        "name": "ssm_scan_kernel_pct.train", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "state-space layers",
        "moves": "train_tokens_per_s",
        "workloads": ["train_nemo3nano_s8192"]}
    assert spec["run_seconds"] == 30
    assert [(m["name"], m["bound"]) for m in spec["end_to_end"]] == [
        ("train_tokens_per_s", 0.01), ("setup_s", 0.1)]
    # a list that named a cell before names it still, first
    for m in spec["end_to_end"] + spec["per_layer"][:-3]:
        if "workloads" in m:
            assert m["workloads"][0] == "train_gpt2m_s1024" or \
                m["workloads"][0] == "train_nemo3nano_s8192"


def test_the_new_cell_joins_what_the_issue_lists():
    spec = harness.benchmark_spec()
    reports = set(harness.metric_names(spec, "per_layer", CELL))
    assert {"linear_attn_ms.train", "linear_attn_scan_roofline_pct.train",
            "moe_gated_experts_roofline_pct.train", "moe_ms.train",
            "moe_padded_rows_pct.train", "flash_attn_ms.train",
            "flash_attn_roofline_pct.train", "train_step_ms",
            "train_mfu_pct", "scope_coverage_pct.train",
            "compiles_in_window"} <= reports
    # other Mosaic calls in the step; no ``mlp`` role; N's pattern string
    assert not {"flash_ms.train", "flash_roofline_pct.train", "mlp_ms.train",
                "flash_packed_pct.train", "ssm_ms.train",
                "ssm_scan_roofline_pct.train", "ssm_scan_kernel_pct.train",
                "moe_experts_roofline_pct.train"} & reports
    assert harness.metric_names(spec, "end_to_end", CELL) == [
        "train_tokens_per_s", "setup_s"]
    cell = harness.find_workload(spec, CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    traffic = harness.traffic_of(cell)
    assert (traffic["batch"], traffic["seq"], traffic["ring"]) == (
        1, 16384, 8)
    for name in ("linear_attn_ms.train",
                 "linear_attn_scan_roofline_pct.train",
                 "moe_gated_experts_roofline_pct.train"):
        entry = [m for m in spec["per_layer"] if m["name"] == name][0]
        assert entry["workloads"] == [CELL]
        # nothing to read, nothing raised: no trace, another kind of run
        read = harness.layer_reader(name)
        assert read({}, None, {"kind": "train"}) is None
        assert read({}, {"devices": {}}, {"kind": "serve_open"}) is None
