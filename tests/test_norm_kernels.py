"""Pallas LayerNorm/softmax kernel parity (interpret mode on CPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels.norm_pallas import (layer_norm_pallas,
                                            softmax_pallas)


def _ref_ln(x, g, b, eps=1e-5):
    x32 = x.astype(np.float32)
    mean = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return (x32 - mean) / np.sqrt(var + eps) * g + b


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_layer_norm_forward_parity(dtype):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 256).astype(np.float32), dtype)
    g = jnp.asarray(rng.randn(256).astype(np.float32))
    b = jnp.asarray(rng.randn(256).astype(np.float32))
    out = layer_norm_pallas(x, g, b, 1e-5, 32, True)
    want = _ref_ln(np.asarray(x, np.float32), np.asarray(g), np.asarray(b))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), want, atol=tol,
                               rtol=tol)


def test_layer_norm_grads_parity():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(32, 128).astype(np.float32))
    g = jnp.asarray(rng.randn(128).astype(np.float32))
    b = jnp.asarray(rng.randn(128).astype(np.float32))
    do = jnp.asarray(rng.randn(32, 128).astype(np.float32))

    def pallas_loss(x, g, b):
        return jnp.sum(layer_norm_pallas(x, g, b, 1e-5, 16, True) * do)

    def ref_loss(x, g, b):
        x32 = x.astype(jnp.float32)
        mean = x32.mean(-1, keepdims=True)
        var = x32.var(-1, keepdims=True)
        xhat = (x32 - mean) * jax.lax.rsqrt(var + 1e-5)
        return jnp.sum((xhat * g + b) * do)

    gp = jax.grad(pallas_loss, argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(x, g, b)
    for a, w, name in zip(gp, gr, "x g b".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


def test_layer_norm_3d_and_row_fallback():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 24, 128).astype(np.float32))
    g = jnp.ones((128,), jnp.float32)
    b = jnp.zeros((128,), jnp.float32)
    out = layer_norm_pallas(x, g, b, 1e-5, 256, True)  # 48 rows < 256 block
    want = _ref_ln(np.asarray(x), np.asarray(g), np.asarray(b))
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError):
        layer_norm_pallas(jnp.zeros((4, 100)), jnp.zeros(100),
                          jnp.zeros(100), 1e-5, 4, True)


def test_softmax_parity():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(48, 256).astype(np.float32) * 5)
    out = softmax_pallas(x, 16, True)
    want = jax.nn.softmax(x, axis=-1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-6, rtol=1e-5)
    s = np.asarray(out).sum(-1)
    np.testing.assert_allclose(s, 1.0, rtol=1e-5)


def test_flag_routes_layer_norm_through_pallas():
    """FLAGS_use_pallas_norm routes nn.functional.layer_norm to the Mosaic
    kernel.  Off a TPU that is an error naming the backend — the library
    never drops into the Pallas interpreter by itself; a test asks for it
    by argument, and the kernel then matches the XLA path."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.kernels.norm_pallas import layer_norm_pallas

    x = paddle.to_tensor(np.random.RandomState(0).randn(16, 128).astype(
        np.float32))
    ln = nn.LayerNorm(128)
    base = ln(x).numpy()
    paddle.set_flags({"FLAGS_use_pallas_norm": True})
    try:
        with pytest.raises(RuntimeError, match="runs on 'cpu'"):
            ln(x)
    finally:
        paddle.set_flags({"FLAGS_use_pallas_norm": False})
    got = layer_norm_pallas(x._array, ln.weight._array, ln.bias._array,
                            1e-5, interpret=True)
    np.testing.assert_allclose(np.asarray(got), base, atol=1e-5, rtol=1e-5)
