"""Test configuration: run on a virtual 8-device CPU mesh so multi-chip
sharding paths execute without TPU hardware (SURVEY.md §4 — the analogue of
the reference's multi-process-on-one-host distributed test pattern)."""
import os

# Force an 8-virtual-device CPU backend for tests.  The backend initializes
# lazily — os.environ XLA_FLAGS + jax.config apply as long as no computation
# ran yet (importing paddle_tpu runs none; tests/test_bringup.py pins that).
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# full-f32 accumulations so numpy/torch parity checks are meaningful
jax.config.update("jax_default_matmul_precision", "highest")
# The suite builds the same tiny programs over and over behind fresh jit
# objects; XLA's persistent cache turns every repeat into a disk hit (a cold
# run of test_paged.py: 53 s -> 37 s, a warm one 18 s).  Executables are
# keyed by their HLO, so nothing a test asserts can change.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache", "tests"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu
    paddle_tpu.seed(2024)
    yield


#: Tests the benchmark's own directory pins and this tree can no longer meet,
#: by node id, with why.  ``tests/benchmarks/`` is the yardstick's: a PR that
#: is no ``benchmark`` PR may add files there and edit none, so a pin that an
#: appended entry breaks is marked here until a ``benchmark`` PR repairs it.
STALE_BENCHMARK_PINS = {
    "tests/benchmarks/test_bench_ssm_scan_kernel.py::"
    "test_the_entry_is_the_hybrid_cells_alone":
        "PR 34 pinned its per-layer entry as the LAST of BENCHMARK.json's "
        "list; every later PR appends after it (PR 35: three entries).  The "
        "entry itself is held unchanged by tests/benchmarks/"
        "test_bench_qwen3_next.py::test_the_entries_before_this_pr_stand",
    "tests/benchmarks/test_bench_qwen3_next.py::"
    "test_the_entries_before_this_pr_stand":
        "PR 35 pinned its three per-layer entries as the LAST of "
        "BENCHMARK.json's list and PR 34's as the fourth from the end; PR 36 "
        "appends one.  The same facts are held by name in tests/benchmarks/"
        "test_bench_linear_attn_scan_kernel.py",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        why = STALE_BENCHMARK_PINS.get(item.nodeid)
        if why:
            item.add_marker(pytest.mark.xfail(reason=why, strict=False))
