"""``ssm_scan_kernel_pct.train``: the share of the traced scans that ran the
Pallas kernels, from a registry snapshot; 0 on a program with the counter
and no kernels (the parent of the PR that added them), nothing where the
counter is missing or outside a training run; the entry that names it."""
import pytest

from benchmarks.lib import harness

NAME = "ssm_scan_kernel_pct.train"


def snap(**calls):
    return {"ssm.scan_calls": {"series": [
        {"labels": {"path": path}, "value": float(n)}
        for path, n in calls.items()]}}


@pytest.mark.parametrize("registry,kind,want", [
    (snap(pallas=8), "train", 100.0),
    (snap(chunked_jnp=8), "train", 0.0),            # the parent
    (snap(pallas=6, chunked_jnp=2), "train", 75.0),
    ({}, "train", None),                            # no such counter
    (None, "train", None),
    (snap(), "train", None),                        # no scan traced
    (snap(pallas=8), "serve_open", None)])
def test_the_reader_reads_the_counter_or_nothing(registry, kind, want):
    read = harness.layer_reader(NAME)
    assert read(registry, None, {"kind": kind}) == want


def test_the_entry_is_the_hybrid_cells_alone():
    spec = harness.benchmark_spec()
    (entry,) = [m for m in spec["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "state-space layers",
        "moves": "train_tokens_per_s",
        "workloads": ["train_nemo3nano_s8192"]}
    assert spec["per_layer"][-1] is entry          # appended
