"""What ``test_the_benchmarks_cells_are_a_subset_of_the_rehearsed`` guards,
for a benchmark that grows by additions: that test demands that the reader
files equal the rehearsal spec's names, so it is red as soon as a PR adds a
reader, and neither it nor ``rehearsal_spec.json`` may be edited by such a
PR.  Here: every reader file is named by ``BENCHMARK.json`` or by the
rehearsal spec and is callable; every ``BENCHMARK.json`` entry that the
rehearsal spec also holds equals it; every entry has its reader."""
import os

import pytest

from benchmarks.lib import harness

REHEARSAL_SPEC = os.path.join(harness.ROOT, "tests", "benchmarks",
                              "rehearsal_spec.json")
ADDED_BY_PR_25 = [
    "optimizer_ms.train", "attention_ms.train", "mlp_ms.train",
    "head_loss_ms.train", "scope_coverage_pct.train", "entry_trace_s",
    "entry_lower_s", "entry_load_s", "unwatched_programs", "pkg_import_s"]


@pytest.fixture(scope="module")
def spec():
    return harness.benchmark_spec()


@pytest.fixture(scope="module")
def rehearsal_spec():
    return harness.benchmark_spec(REHEARSAL_SPEC)


def reader_files():
    return sorted(f[:-3] for f in os.listdir(os.path.join(
        harness.BENCH_DIR, "layer_metrics")) if f.endswith(".py"))


def test_every_reader_file_is_named_and_callable(spec, rehearsal_spec):
    named = ({m["name"] for m in spec["per_layer"]}
             | {m["name"] for m in rehearsal_spec["per_layer"]})
    assert set(reader_files()) <= named
    for name in reader_files():
        assert callable(harness.layer_reader(name))


def test_every_entry_has_its_reader_file(spec):
    assert {m["name"] for m in spec["per_layer"]} <= set(reader_files())


def test_entries_the_rehearsal_spec_also_holds_equal_it(spec,
                                                        rehearsal_spec):
    for section in ("end_to_end", "per_layer"):
        known = {m["name"]: m for m in rehearsal_spec[section]}
        for m in spec[section]:
            if m["name"] in known:
                assert {k: v for k, v in m.items() if k != "bound"} == {
                    k: v for k, v in known[m["name"]].items()
                    if k != "bound"}
    assert spec["workloads"][0] in rehearsal_spec["workloads"]
    assert spec["run_seconds"] == rehearsal_spec["run_seconds"]


@pytest.mark.parametrize("name", ADDED_BY_PR_25)
def test_an_added_metric_is_an_entry_appended_for_the_train_cell(spec, name):
    entry = {m["name"]: m for m in spec["per_layer"]}[name]
    assert entry["workloads"] == ["train_gpt2m_s1024"]
    device = name.endswith(".train")
    assert entry["source"] == ("device_trace" if device else
                               "program_counter"
                               if name == "unwatched_programs"
                               else "program_span")
    assert entry["moves"] == ("train_tokens_per_s" if device else "setup_s")
    # appended: after every metric the accepted benchmark had
    order = [m["name"] for m in spec["per_layer"]]
    assert order.index(name) > order.index("hbm_peak_gb.train")
