"""``run.py`` end to end: a rehearsal of every traffic kind (and of
``kind: train`` under a dp 2 x mp 2 mesh on four virtual CPU devices, and of
a cell of a model family that only the tests know), the key set of the last
line, the refusal to measure without a TPU, and ``BENCHMARK.json`` against
the limits of its contract, as it stands and as a later PR may grow it
(``conftest.py``: the ``spec`` fixture)."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench_support import same_but_grown
from benchmarks.lib import harness

ROOT = harness.ROOT
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def run_py(*argv, env=None):
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    full_env.pop("XLA_FLAGS", None)     # the child sets its own device count
    full_env.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *argv],
        cwd=ROOT, env=full_env, capture_output=True, text=True, timeout=600)


def lines_of(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]


REHEARSAL_SPEC = os.path.join(ROOT, "tests", "benchmarks",
                              "rehearsal_spec.json")


@pytest.mark.parametrize("workload,trace,seed", [
    ("train_gpt2m_s1024", 0, 2 ** 31 + 252),
    ("train_gpt2m_s1024", 1, 5),
    ("serve_c13b_chat", 0, 4_000_000_007),
    ("serve_gpt2m_longgen", 1, 17),
])
def test_rehearsal_end_to_end(rehearsal_spec, workload, trace, seed):
    spec = rehearsal_spec
    lines = lines_of(run_py("--workload", workload, "--seed", str(seed),
                            "--seconds", "2", "--trace", str(trace),
                            "--rehearse", "--spec", REHEARSAL_SPEC))
    result = lines[-1]
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    # a CPU run never prints a number under a device metric's name
    assert result["metrics"] == {} and result["rehearsal"] is True
    assert all(l.get("rehearsal") for l in lines)
    section = "per_layer" if trace else "end_to_end"
    assert set(result["rehearsed_metric_names"]) <= set(
        harness.metric_names(spec, section, workload))
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    parts = {l["part"] for l in lines if l.get("phase") == "setup"}
    assert {"import", "model_build", "weights_from_seed", "warmup",
            "total"} <= parts
    window = [l for l in lines if l.get("phase") == "window"][0]
    assert window["programs_in_window"]["cache_misses"] == 0
    assert window["programs_in_window"]["cache_hits"] == 0
    checked = [l for l in lines if l.get("phase") == "check"][0]
    assert checked["within"] is True and checked["seconds"] >= 0


def mesh_spec_file(tmp_path, spec):
    """``spec`` with one cell, ``train_mesh``: the train cell's traffic
    under a dp 2 x mp 2 mesh, on cerebras-gpt-1.3b, four chips."""
    traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                "train_gpt2m_s1024.json")
    traffic["mesh"] = {"dp": 2, "mp": 2}
    traffic_file = tmp_path / "train_mesh.json"
    traffic_file.write_text(json.dumps(traffic))
    trial = dict(spec, workloads=[{
        "name": "train_mesh", "config": "cerebras-gpt-1.3b",
        "traffic": "train_mesh", "chips": 4, "why": "rehearsal",
        "traffic_file": str(traffic_file)}])
    trial["end_to_end"] = [dict(m, workloads=["train_mesh"])
                           if m["name"] == "train_tokens_per_s" else m
                           for m in spec["end_to_end"]]
    spec_file = tmp_path / "spec.json"
    spec_file.write_text(json.dumps(trial))
    return str(spec_file)


def test_mesh_cell_is_data(tmp_path, rehearsal_spec):
    """train_c13b_dp2mp2 can be added as two data files and one entry: a
    traffic file with ``mesh`` runs the hybrid path, rehearsed here on four
    virtual CPU devices.  (Traced, gradients compared:
    ``test_bench_check_memory.py``.)"""
    lines = lines_of(run_py("--workload", "train_mesh", "--seed", "3",
                            "--seconds", "2", "--trace", "0", "--rehearse",
                            "--spec",
                            mesh_spec_file(tmp_path, rehearsal_spec)))
    assert lines[-1]["correct"] is True
    assert lines[-1]["device"]["count"] == 4
    assert "train_tokens_per_s" in lines[-1]["rehearsed_metric_names"]


def rehearse_toy(grown_spec_file, seed, env=None):
    from bench_support import TOY_CELL
    return run_py("--workload", TOY_CELL, "--seed", str(seed), "--seconds",
                  "1", "--trace", "1", "--rehearse", "--spec",
                  grown_spec_file, env=env)


def test_a_cell_of_another_family_is_files(grown_spec_file):
    """RMSNorm, a gated feed-forward, no positions, an untied head and a
    convolution for a mixer, with its own reference, initialisation and
    FLOP count, all among the tests' files: the harness runs it to
    ``correct`` at batch 1 with ``check_rows`` 1, gradients compared, and no
    code or data file under ``benchmarks/`` names it."""
    proc = rehearse_toy(grown_spec_file, 2 ** 31 + 7)
    lines = lines_of(proc)
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == {"loss_rel", "logits_rel_rms",
                                       "grad_rel_worst"}
    assert all(v["value"] < v["limit"] for v in result["compared"].values())
    # the same numbers beside their limits close stderr
    tail = proc.stderr.strip().splitlines()[-4:]
    assert [l.split(":")[0] for l in tail] == [
        "compared loss_rel", "compared logits_rel_rms",
        "compared grad_rel_worst", "correct"]
    window = [l for l in lines if l.get("phase") == "window"][0]
    assert window["batch"][0] == 1
    checked = [l for l in lines if l.get("phase") == "check"][0]
    assert checked["gradients"]["tensor"].startswith(
        ("blocks.", "embed.", "head.", "norm_f."))
    for folder, _, files in os.walk(harness.BENCH_DIR):
        for f in files:
            if f.endswith((".py", ".json")):     # code and data; not prose
                with open(os.path.join(folder, f)) as fh:
                    assert "convmix" not in fh.read(), f


def test_a_reference_without_the_mixer_reads_not_correct(grown_spec_file):
    """The control of the toy family: its reference with the mixer dropped
    must fail what the whole reference passes."""
    lines = lines_of(rehearse_toy(
        grown_spec_file, 2 ** 31 + 7,
        env={"BENCH_CONVMIX_REFERENCE_DROPS_MIXER": "1"}))
    assert lines[-1]["correct"] is False
    compared = lines[-1]["compared"]
    assert compared["logits_rel_rms"]["value"] > 2 * compared[
        "logits_rel_rms"]["limit"]
    assert compared["grad_rel_worst"]["value"] > 2 * compared[
        "grad_rel_worst"]["limit"]


def test_an_unknown_family_no_result(tmp_path):
    config = harness.load_json(ROOT, "benchmarks", "configs",
                               "gpt2-medium.json")
    config_file = tmp_path / "config.json"
    config_file.write_text(json.dumps(dict(config, family="nonesuch")))
    trial = harness.benchmark_spec()
    trial["configs"] = [dict(trial["configs"][0], file=str(config_file))]
    spec_file = tmp_path / "spec.json"
    spec_file.write_text(json.dumps(trial))
    proc = run_py("--workload", "train_gpt2m_s1024", "--seed", "1",
                  "--seconds", "1", "--trace", "0", "--rehearse", "--spec",
                  str(spec_file))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no model family 'nonesuch'" in proc.stderr


def test_no_tpu_no_result():
    proc = run_py("--workload", "train_gpt2m_s1024", "--seed", "1",
                  "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_unknown_workload_no_result():
    proc = run_py("--workload", "nonesuch", "--seed", "1", "--seconds", "1",
                  "--trace", "0", "--rehearse")
    assert proc.returncode != 0 and proc.stdout.strip() == ""


# -- BENCHMARK.json against its contract ----------------------------------------

def test_spec_keys_and_limits(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    # a full check at the full 24 cells fits into 43,200 s
    runs = 2 + 14 * 24
    assert (runs * (spec["run_seconds"] + 60) + 24 * 2 * 90 + 1200) <= 43200
    assert spec["command"][1].startswith(spec["paths"][0] + "/")
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 4)


def test_spec_names_units_and_bounds(spec):
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in spec[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((section[:3] if section in ("configs", "workloads")
                          else "metric", entry["name"]))
    assert len(names) == len(set(names))
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in spec["end_to_end"])
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    layers = set()
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.add(m["layer"])
        # the metric it moves is reported wherever this one is
        moved = e2e[m["moves"]]
        mine = set(m.get("workloads", cells))
        assert mine <= cells and mine <= set(moved.get("workloads", cells))
    assert len(layers) <= 12


def test_every_cell_and_metric_has_its_files(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    used = set()
    for w in spec["workloads"]:
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
        used.add(w["config"])
        traffic = harness.traffic_of(w)
        assert traffic["kind"] in ("train", "serve_open", "serve_closed")
        assert "rehearse" in traffic
        assert harness.metric_names(spec, "per_layer", w["name"])
        assert len(harness.metric_names(spec, "end_to_end", w["name"])) >= 2
    assert used == set(configs)
    for c in configs.values():
        assert c["file"].startswith(tuple(p + "/" for p in spec["paths"]))
        held = harness.load_json(ROOT, c["file"])
        assert held["source"] == c["source"]
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        assert len(c["why"]) <= 200 and len(c["reduced"]) <= 16
        # no width is ever cut
        assert not any(k.endswith(("_dim", "_rank")) or "hidden_size" in k
                       or "intermediate" in k for k in c["reduced"])
        # what the source states against what is run, by the family's own
        # key names: equal, unless ``reduced`` says the key was changed
        family = harness.load_family(held.get("family", "gpt"),
                                     c.get("family_file"))
        pairs = family.width_pairs(held)
        assert pairs
        for key, as_run, published in pairs:
            assert as_run == published or key in c["reduced"], key
        assert held["token_id_limit"] <= family.vocab_size(
            held[family.MODEL_KEY])
        as_rehearsed = harness.as_run(held, True, c.get("family_file"))
        assert as_rehearsed["token_id_limit"] <= family.vocab_size(
            as_rehearsed["model"])
    for m in spec["per_layer"]:
        assert callable(harness.layer_reader(m["name"]))


def test_the_benchmarks_cells_are_a_subset_of_the_rehearsed(spec,
                                                            rehearsal_spec):
    """The reader files are a superset of what the rehearsal spec names
    and every one is named by an entry; what BENCHMARK.json holds of the
    rehearsal spec is in it entry for entry, grown at most by cells."""
    readers = {f[:-3] for f in os.listdir(os.path.join(
        harness.BENCH_DIR, "layer_metrics")) if f.endswith(".py")}
    rehearsed = {m["name"] for m in rehearsal_spec["per_layer"]}
    assert rehearsed <= readers
    assert readers <= rehearsed | {m["name"] for m in spec["per_layer"]}
    for name in readers:
        assert callable(harness.layer_reader(name))
    for section in ("end_to_end", "per_layer"):
        known = {m["name"]: m for m in rehearsal_spec[section]}
        for m in spec[section]:
            if m["name"] in known:
                assert same_but_grown(m, known[m["name"]]), m["name"]
    assert spec["workloads"][0] in rehearsal_spec["workloads"]
    assert spec["run_seconds"] == rehearsal_spec["run_seconds"]


def test_a_reader_with_nothing_to_read_returns_nothing(rehearsal_spec):
    for m in rehearsal_spec["per_layer"]:
        if m["source"] == "device_trace":
            assert harness.layer_reader(m["name"])(
                {}, None, {"kind": "train", "rehearsal": True}) is None
