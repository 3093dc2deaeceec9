"""The flash kernels of the main path, compiled for a described v5e at the
benchmark's real widths — no chip needed, about ten seconds each.  The
Pallas interpreter accepts what Mosaic refuses (a (1, t) store at a lane
offset into a dynamically indexed row, more VMEM than a kernel may use):
PR 26's sub-tiled band met both only here.  One file, topology inside a
fixture (only one process may hold the TPU library; see the
on-chip-measurement guide)."""
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu.kernels.flash_attention_pallas as fap


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


# (b, s, h, d, resident forward + merged backward?)
CELLS = [
    pytest.param(16, 1024, 16, 64, True, id="gpt2-medium-s1024"),
    pytest.param(4, 2048, 16, 128, True, id="cerebras-1.3b-s2048"),
    pytest.param(1, 16384, 16, 64, False, id="gpt2-medium-s16384-streamed"),
]


@pytest.mark.parametrize("b,s,h,d,short", CELLS)
def test_causal_flash_compiles_for_v5e(one_chip, no_persistent_cache, b, s,
                                       h, d, short):
    hg_b = fap._pick_head_group(h, d, s)
    hg_f = fap._pick_fwd_head_group(h, d, s, hg_b)
    bq, bk = fap._prep_blocks(s, s, True, fap.DEFAULT_BLOCK_Q,
                              fap.DEFAULT_BLOCK_K, "test")
    fwd_spec, bwd_spec = fap._resolve_specs(
        b, s, s, h, d, jnp.bfloat16, True, bq, bk, hg_f, hg_b,
        use_autotune=False)
    assert (bwd_spec[0] == "merged") == short
    assert fap._kv_fits_resident(s, hg_f * d) == short
    scale = 1.0 / d ** 0.5

    def step(q, k, v, do):
        out, lse = fap._flash_fwd(q, k, v, True, scale, d, False, fwd_spec)
        return fap._flash_bwd(q, k, v, out, lse, do, True, scale, d, False,
                              bwd_spec)

    x = jax.ShapeDtypeStruct((b, s, h * d), jnp.bfloat16, sharding=one_chip)
    # the suite asks for f32 matmul passes (conftest); the chip runs the
    # default precision, and Mosaic has no f32 pass over bf16 operands
    with jax.default_matmul_precision("default"):
        text = jax.jit(step).lower(x, x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (2 if short else 3)
