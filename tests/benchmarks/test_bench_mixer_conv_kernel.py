"""``mixer_conv_kernel_pct.train``: the share of the traced calls of what
stands in front of the recurrent scans (convolution, SiLU, split,
normalisation) that ran the Pallas kernels, from a registry snapshot; 0 on
a program with the counter and the ``jnp`` path only, nothing where the
counter is missing (the parent of the PR that added it) or outside a
training run; the entry that names it, found by name wherever later PRs'
entries put it."""
import pytest

from benchmarks.lib import harness

NAME = "mixer_conv_kernel_pct.train"
CELLS = ["train_nemo3nano_s8192", "train_qwen3next_s16384"]


def snap(**calls):
    return {"ssm.conv_calls": {"series": [
        {"labels": {"path": path}, "value": float(n)}
        for path, n in calls.items()]}}


@pytest.mark.parametrize("registry,kind,want", [
    (snap(pallas=8), "train", 100.0),
    (snap(jnp=8), "train", 0.0),
    (snap(pallas=6, jnp=2), "train", 75.0),
    ({}, "train", None),                            # the parent: no counter
    (None, "train", None),
    (snap(), "train", None),                        # no mixer traced
    ({"ssm.scan_calls": {"series": [                # another counter's
        {"labels": {"path": "pallas"}, "value": 8.0}]}}, "train", None),
    (snap(pallas=8), "serve_open", None)])
def test_the_reader_reads_the_counter_or_nothing(registry, kind, want):
    read = harness.layer_reader(NAME)
    assert read(registry, None, {"kind": kind}) == want


def test_the_entry_is_the_two_recurrent_cells():
    spec = harness.benchmark_spec()
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "state-space layers",
        "moves": "train_tokens_per_s", "workloads": CELLS}
    for cell in CELLS:
        assert NAME in harness.metric_names(spec, "per_layer", cell)
    assert NAME not in harness.metric_names(spec, "per_layer",
                                            "train_gpt2m_s1024")


# (name, layer, cells): the scans' own shares, which this PR's kernels stay
# outside of, and the sums its claim is read from
EARLIER = [
    ("ssm_ms.train", "state-space layers", CELLS[:1]),
    ("ssm_scan_roofline_pct.train", "state-space layers", CELLS[:1]),
    ("ssm_scan_kernel_pct.train", "state-space layers", CELLS[:1]),
    ("linear_attn_ms.train", "linear-attention layers", CELLS[1:]),
    ("linear_attn_scan_roofline_pct.train", "linear-attention layers",
     CELLS[1:]),
    ("linear_attn_scan_kernel_pct.train", "linear-attention layers",
     CELLS[1:]),
    ("recompute_ms.train", "train step", CELLS),
    ("data_movement_ms.train", "train step", ["train_gpt2m_s1024"] + CELLS)]


@pytest.mark.parametrize("name,layer,cells", EARLIER)
def test_the_entries_before_this_pr_stand(name, layer, cells):
    spec = harness.benchmark_spec()
    names = [m["name"] for m in spec["per_layer"]]
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    assert (entry["layer"], entry["workloads"], entry["moves"]) == (
        layer, cells, "train_tokens_per_s")
    assert names.index(name) < names.index(NAME)


def test_nothing_else_of_the_benchmark_moved():
    spec = harness.benchmark_spec()
    assert [c["name"] for c in spec["configs"]] == [
        "gpt2-medium", "nemotron-3-nano-30b-a3b", "qwen3-next-80b-a3b"]
    assert [w["name"] for w in spec["workloads"]] == [
        "train_gpt2m_s1024"] + CELLS
    assert spec["run_seconds"] == 30
    assert [(m["name"], m["bound"]) for m in spec["end_to_end"]] == [
        ("train_tokens_per_s", 0.01), ("setup_s", 0.1)]
