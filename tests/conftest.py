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
