"""``run.py`` end to end: a rehearsal of every traffic kind (and of
``kind: train`` under a dp 2 x mp 2 mesh on four virtual CPU devices), the
key set of the last line, the refusal to measure without a TPU, and
``BENCHMARK.json`` against the limits of its contract."""
import json
import os
import re
import subprocess
import sys

import pytest

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


@pytest.fixture(scope="module")
def spec():
    return harness.benchmark_spec()


@pytest.fixture(scope="module")
def rehearsal_spec():
    """The issue's three cells: the one in BENCHMARK.json and the two
    serving cells that wait, as data, among the tests' fixtures."""
    return harness.benchmark_spec(REHEARSAL_SPEC)


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


def test_mesh_cell_is_data(tmp_path, rehearsal_spec):
    """train_c13b_dp2mp2 can be added as two data files and one entry: a
    traffic file with ``mesh`` runs the hybrid path, rehearsed here on four
    virtual CPU devices."""
    traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                "train_gpt2m_s1024.json")
    traffic["mesh"] = {"dp": 2, "mp": 2}
    traffic_file = tmp_path / "train_mesh.json"
    traffic_file.write_text(json.dumps(traffic))
    spec = rehearsal_spec
    trial = dict(spec, workloads=[{
        "name": "train_mesh", "config": "cerebras-gpt-1.3b",
        "traffic": "train_mesh", "chips": 4, "why": "rehearsal",
        "traffic_file": str(traffic_file)}])
    trial["end_to_end"] = [dict(m, workloads=["train_mesh"])
                           if m["name"] == "train_tokens_per_s" else m
                           for m in spec["end_to_end"]]
    spec_file = tmp_path / "spec.json"
    spec_file.write_text(json.dumps(trial))
    lines = lines_of(run_py("--workload", "train_mesh", "--seed", "3",
                            "--seconds", "2", "--trace", "0", "--rehearse",
                            "--spec", str(spec_file)))
    assert lines[-1]["correct"] is True
    assert lines[-1]["device"]["count"] == 4
    assert "train_tokens_per_s" in lines[-1]["rehearsed_metric_names"]


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
        assert c["file"].startswith("benchmarks/configs/")
        held = harness.load_json(ROOT, c["file"])
        assert held["source"] == c["source"]
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        assert len(c["why"]) <= 200 and len(c["reduced"]) <= 16
        # no width is ever cut
        assert not any(k.endswith(("_dim", "_rank")) or "hidden" in k
                       or "intermediate" in k for k in c["reduced"])
        g, p = held["gpt_config"], held["published"]
        assert g["hidden_size"] == p["n_embd"]
        assert g["num_hidden_layers"] == p["n_layer"]
        assert g["num_attention_heads"] == p["n_head"]
        assert g["intermediate_size"] == (p["n_inner"] or 4 * p["n_embd"])
        assert g["max_position_embeddings"] == p["n_positions"]
    for m in spec["per_layer"]:
        assert callable(harness.layer_reader(m["name"]))


def test_the_benchmarks_cells_are_a_subset_of_the_rehearsed(spec,
                                                            rehearsal_spec):
    """Every reader file is named by the rehearsal spec, and what
    BENCHMARK.json holds is in it entry for entry."""
    readers = {f[:-3] for f in os.listdir(os.path.join(
        harness.BENCH_DIR, "layer_metrics")) if f.endswith(".py")}
    assert readers == {m["name"] for m in rehearsal_spec["per_layer"]}
    for name in readers:
        assert callable(harness.layer_reader(name))
    for section in ("end_to_end", "per_layer"):
        known = {m["name"]: m for m in rehearsal_spec[section]}
        for m in spec[section]:
            assert {k: v for k, v in m.items() if k != "bound"} == {
                k: v for k, v in known[m["name"]].items() if k != "bound"}
    assert spec["workloads"][0] in rehearsal_spec["workloads"]
    assert spec["run_seconds"] == rehearsal_spec["run_seconds"]


def test_a_reader_with_nothing_to_read_returns_nothing(rehearsal_spec):
    for m in rehearsal_spec["per_layer"]:
        if m["source"] == "device_trace":
            assert harness.layer_reader(m["name"])(
                {}, None, {"kind": "train", "rehearsal": True}) is None
