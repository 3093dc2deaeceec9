"""``linear_attn_scan_kernel_pct.train``: the share of the traced delta
rules that ran the Pallas kernels, from a registry snapshot; 0 on a program
with the counter and no kernels (the parent of the PR that added them),
nothing where the counter is missing or outside a training run; the entry
that names it, found by name wherever later PRs' entries put it."""
import pytest

from benchmarks.lib import harness

NAME = "linear_attn_scan_kernel_pct.train"
CELL = "train_qwen3next_s16384"


def snap(**calls):
    return {"linear_attn.scan_calls": {"series": [
        {"labels": {"path": path}, "value": float(n)}
        for path, n in calls.items()]}}


@pytest.mark.parametrize("registry,kind,want", [
    (snap(pallas=9), "train", 100.0),
    (snap(chunked_jnp=9), "train", 0.0),            # the parent
    (snap(pallas=6, chunked_jnp=2), "train", 75.0),
    ({}, "train", None),                            # no such counter
    (None, "train", None),
    (snap(), "train", None),                        # no rule traced
    (snap(pallas=9), "serve_open", None)])
def test_the_reader_reads_the_counter_or_nothing(registry, kind, want):
    read = harness.layer_reader(NAME)
    assert read(registry, None, {"kind": kind}) == want


def test_the_entry_is_the_linear_attention_cells_alone():
    spec = harness.benchmark_spec()
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "linear-attention layers",
        "moves": "train_tokens_per_s", "workloads": [CELL]}
    assert NAME in harness.metric_names(spec, "per_layer", CELL)
    for other in ("train_gpt2m_s1024", "train_nemo3nano_s8192"):
        assert NAME not in harness.metric_names(spec, "per_layer", other)


# (name, layer, cells): what PRs 34 and 35 appended, wherever it stands now
EARLIER = [
    ("ssm_scan_kernel_pct.train", "state-space layers",
     ["train_nemo3nano_s8192"]),
    ("linear_attn_ms.train", "linear-attention layers", [CELL]),
    ("linear_attn_scan_roofline_pct.train", "linear-attention layers",
     [CELL]),
    ("moe_gated_experts_roofline_pct.train", "expert layers", [CELL])]


@pytest.mark.parametrize("name,layer,cells", EARLIER)
def test_the_entries_before_this_pr_stand(name, layer, cells):
    """``test_bench_qwen3_next.py`` holds these by their place from the
    end of the list, which this PR's appended entry moves: the same facts
    by name, and the order of what was there kept."""
    spec = harness.benchmark_spec()
    names = [m["name"] for m in spec["per_layer"]]
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    assert (entry["layer"], entry["workloads"], entry["moves"]) == (
        layer, cells, "train_tokens_per_s")
    assert names.index(name) < names.index(NAME)
    assert [n for n in names if n in [e[0] for e in EARLIER]] == [
        e[0] for e in EARLIER]


def test_nothing_else_of_the_benchmark_moved():
    spec = harness.benchmark_spec()
    assert [c["name"] for c in spec["configs"]] == [
        "gpt2-medium", "nemotron-3-nano-30b-a3b", "qwen3-next-80b-a3b"]
    assert [w["name"] for w in spec["workloads"]] == [
        "train_gpt2m_s1024", "train_nemo3nano_s8192", CELL]
    assert spec["run_seconds"] == 30
    assert [(m["name"], m["bound"]) for m in spec["end_to_end"]] == [
        ("train_tokens_per_s", 0.01), ("setup_s", 0.1)]
