"""What the chip bring-up guarantees, as far as a CPU can check it: a
backend-free import, a compile cache placed from outside, no device handed out
under a wrong name, the smoke's refusal to run without a TPU, and the flash
kernel partitioned over a dp x mp mesh."""
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = pathlib.Path(__file__).resolve().parent.parent


def _python(code, timeout=300, **env):
    """Run ``code`` in a fresh interpreter at the repo root, CPU only."""
    full_env = {k: v for k, v in os.environ.items()
                if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    full_env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}, **env)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          cwd=REPO, env=full_env, capture_output=True,
                          text=True, timeout=timeout)


def test_import_starts_no_backend_and_cache_defaults_to_the_checkout():
    """On a TPU host whoever initialises the backend owns the chip: the
    launcher's parent, a CLI beside a live server and a DataLoader worker
    all import the package and must not take it.  Same interpreter: with
    JAX_COMPILATION_CACHE_DIR unset the cache lands in <checkout>/.jax_cache."""
    r = _python("""
        import os
        from jax._src import xla_bridge
        import paddle_tpu
        import paddle_tpu.distributed.launch_main
        import paddle_tpu.kernels.autotune
        import paddle_tpu.observability.__main__
        assert not xla_bridge._backends, list(xla_bridge._backends)
        assert not xla_bridge.backends_are_initialized()
        import jax
        from paddle_tpu.utils.compile_cache import enable_compile_cache
        want = os.path.join(os.getcwd(), ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert not xla_bridge.backends_are_initialized()
        print("OK")
    """)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]


def test_cache_dir_from_the_environment_is_left_alone(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax reads it itself and the
    helper sets no directory in code; programs are written there and
    nowhere else.  (Other workers' tests cache into the default directory
    meanwhile, so "nowhere else" is asked of this test's own program, which
    no other test compiles, and not of the directory's whole listing.)"""
    default_dir = REPO / ".jax_cache"
    r = _python("""
        import os, jax, jax.numpy as jnp
        set_in_code = []
        real = jax.config.update
        jax.config.update = lambda k, v: (set_in_code.append(k), real(k, v))
        from paddle_tpu.utils.compile_cache import enable_compile_cache
        env = os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert enable_compile_cache() == env
        assert "jax_compilation_cache_dir" not in set_in_code, set_in_code
        assert jax.config.jax_compilation_cache_dir == env
        jax.jit(lambda x: jnp.sin(x) @ x + 0.271828)(
            jnp.ones((8, 8))).block_until_ready()
        assert os.listdir(env), "nothing was cached"
        print("OK")
    """, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]
    # the eager helpers (jnp.ones) are anybody's; the jitted lambda is ours
    written = {f for f in os.listdir(tmp_path / "cc")
               if f.startswith("jit__lambda")}
    elsewhere = set(os.listdir(default_dir)) if default_dir.exists() else set()
    assert written and not written & elsewhere, (written, len(elsewhere))


def test_set_device_tpu_raises_without_a_tpu():
    import paddle_tpu as paddle
    with pytest.raises(RuntimeError, match="no TPU"):
        paddle.set_device("tpu")
    with pytest.raises(RuntimeError, match="no TPU"):
        paddle.to_tensor([1.0]).to("tpu:0")
    with pytest.raises(ValueError, match="unknown device"):
        paddle.set_device("npu")
    try:
        assert paddle.set_device("cpu") == "cpu"
        assert paddle.to_tensor([1.0]).to("cpu:1")._array.devices() == {
            jax.devices("cpu")[1]}
    finally:
        jax.config.update("jax_default_device", None)
        paddle.device._current[0] = None


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = _python("import runpy; runpy.run_path('chip_smoke.py', "
                "run_name='__main__')")
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "--rehearse" in r.stderr
    assert r.stdout.strip() == ""          # no result line of any kind


@pytest.mark.slow
def test_chip_smoke_rehearsal_passes_and_says_so(tmp_path):
    import json
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse"], cwd=REPO,
        env={**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(l) for l in r.stdout.splitlines()]
    assert all(l.get("rehearsal") is True for l in lines)
    assert {l.get("phase") for l in lines} >= {"start", "flash_reference",
                                               "train", "serve"}
    summary, last = lines[-2:]
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    # the result line: "ok" and "device" and nothing else (the driver's
    # contract; the rehearsal label is the one extra, and only here)
    assert set(last) == {"rehearsal", "ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"


# -- the flash kernel under a dp x mp mesh --------------------------------------

@pytest.fixture
def dp2_mp2():
    from paddle_tpu.distributed import mesh as mesh_mod
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                             ("dp", "mp"))
    with mesh_mod.mesh_scope(mesh):
        yield mesh


def _qkv(mesh, b=4, s=256, h=4, d=64):
    rng = np.random.RandomState(0)
    sharding = NamedSharding(mesh, P("dp", None, "mp", None))
    return tuple(jax.device_put(jnp.asarray(rng.randn(b, s, h, d),
                                            jnp.float32), sharding)
                 for _ in range(3))


def test_flash_under_a_mesh_runs_per_shard_and_matches_reference(dp2_mp2):
    """A Mosaic call has no GSPMD partitioning rule ("Mosaic kernels cannot
    be automatically partitioned"): under a multi-device mesh the kernel
    goes through the shard_map wrapper, every device attends over its own
    (B/dp, S, H/mp, D) block, and forward and backward match the O(S^2)
    reference."""
    from paddle_tpu.analysis.trace.core import walk_eqns
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels.flash_attention_pallas import _reference_bhsd

    q, k, v = _qkv(dp2_mp2)
    b, s, h, d = q.shape
    assert fa.supported(q, k, interpret=True)
    assert not fa.supported(q, k)          # CPU: only when asked to interpret

    def flash(q, k, v):
        o = fa.flash_attention_bshd(q, k, v, causal=True, interpret=True)
        return jnp.sum(o * o), o

    def reference(q, k, v):
        o = _reference_bhsd(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)),
                            True, 1.0 / np.sqrt(d))
        o = jnp.swapaxes(o, 1, 2)
        return jnp.sum(o * o), o

    grad = jax.jit(jax.value_and_grad(flash, argnums=(0, 1, 2),
                                      has_aux=True))
    sites = list(walk_eqns(grad.trace(q, k, v).jaxpr, into_pallas=False))
    kernels = [st for st in sites if st.eqn.primitive.name == "pallas_call"]
    assert kernels, "no Pallas call traced"
    by_eqn = {id(st.eqn): st for st in sites}

    def enclosing(st):
        """The call around a kernel, past the kernel builder's own jit
        (the builders are jitted so that layers share one trace)."""
        parent = st.parent
        while parent is not None and parent.primitive.name in ("jit",
                                                               "pjit"):
            parent = by_eqn[id(parent)].parent
        return parent

    for st in kernels:
        assert enclosing(st) is not None \
            and enclosing(st).primitive.name == "shard_map", st.path
        assert tuple(st.eqn.invars[0].aval.shape) == (b // 2, s, h * d // 2)

    (_, out), grads = grad(q, k, v)
    (_, out_ref), grads_ref = jax.value_and_grad(
        reference, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert out.sharding.spec == P("dp", None, "mp", None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=2e-4, atol=2e-4)
    for g, g_ref in zip(grads, grads_ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=2e-3, atol=2e-3)


def test_flash_under_a_mesh_rejects_what_it_cannot_shard(dp2_mp2):
    """A head count the mp axis does not divide is an error at the kernel
    entry — not a quiet fall to the O(S^2) reference."""
    from paddle_tpu.kernels import flash_attention as fa
    q = jnp.zeros((4, 256, 3, 64), jnp.float32)
    with pytest.raises(ValueError, match="not divisible"):
        fa.flash_attention_bshd(q, q, q, causal=True, interpret=True)


def test_forked_loader_workers_never_see_device_data():
    """DataLoader workers are forked copies of a process that may own the
    chip: a dataset that hands out Tensors stays on the threaded path."""
    import paddle_tpu as paddle
    from paddle_tpu.io import DataLoader, TensorDataset

    data = TensorDataset([paddle.to_tensor(np.arange(32, dtype=np.float32)
                                           .reshape(16, 2))])
    loader = DataLoader(data, batch_size=4, num_workers=2)
    assert loader._iter_multiprocess() is None
    batches = list(loader)
    assert len(batches) == 4
    np.testing.assert_array_equal(batches[0][0].numpy(),
                                  np.arange(8, dtype=np.float32).reshape(4, 2))
