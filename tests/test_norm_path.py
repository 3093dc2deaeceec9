"""The LayerNorm every block runs (``nn/functional/norm.py``: plain jnp,
statistics in float32, output in the input's type) against a float64 NumPy
reference."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.nn.functional.norm import layer_norm_raw


def _ref_ln(x, g, b, eps=1e-5):
    x = np.asarray(x, np.float64)
    mean = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mean) / np.sqrt(var + eps) * g + b


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_layer_norm_forward_parity(dtype):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 256).astype(np.float32), dtype)
    g = rng.randn(256).astype(np.float32)
    b = rng.randn(256).astype(np.float32)
    out = F.layer_norm(paddle.to_tensor(x), [256], paddle.to_tensor(g),
                       paddle.to_tensor(b), 1e-5)
    assert out._array.dtype == dtype    # bf16 stays bf16 down the stream
    want = _ref_ln(np.asarray(x, np.float32), g, b)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out._array, np.float32), want,
                               atol=tol, rtol=tol)


def test_layer_norm_grads_parity():
    rng = np.random.RandomState(1)
    x = rng.randn(32, 128).astype(np.float32)
    g = rng.randn(128).astype(np.float32)
    b = rng.randn(128).astype(np.float32)
    do = rng.randn(32, 128).astype(np.float32)
    got = jax.grad(lambda x, g, b: jnp.sum(
        layer_norm_raw(x, g, b, (128,), 1e-5) * do), argnums=(0, 1, 2))(
            jnp.asarray(x), jnp.asarray(g), jnp.asarray(b))
    # the textbook backward, in float64
    x64, do64 = x.astype(np.float64), do.astype(np.float64)
    mean = x64.mean(-1, keepdims=True)
    rstd = 1.0 / np.sqrt(x64.var(-1, keepdims=True) + 1e-5)
    xhat = (x64 - mean) * rstd
    dxhat = do64 * g
    dx = rstd * (dxhat - dxhat.mean(-1, keepdims=True)
                 - xhat * (dxhat * xhat).mean(-1, keepdims=True))
    want = (dx, (do64 * xhat).sum(0), do64.sum(0))
    for a, w, name in zip(got, want, "x g b".split()):
        np.testing.assert_allclose(np.asarray(a), w, atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name}")


def test_layer_norm_3d():
    rng = np.random.RandomState(2)
    x = rng.randn(2, 24, 128).astype(np.float32)
    g, b = np.ones((128,), np.float32), np.zeros((128,), np.float32)
    out = F.layer_norm(paddle.to_tensor(x), 128, paddle.to_tensor(g),
                       paddle.to_tensor(b))
    np.testing.assert_allclose(out.numpy(), _ref_ln(x, g, b), atol=1e-5,
                               rtol=1e-5)
    # without weight and bias, over the last two axes
    out = F.layer_norm(paddle.to_tensor(x), [24, 128])
    x64 = x.astype(np.float64)
    mean = x64.mean((-2, -1), keepdims=True)
    var = x64.var((-2, -1), keepdims=True)
    np.testing.assert_allclose(out.numpy(), (x64 - mean) / np.sqrt(var + 1e-5),
                               atol=1e-5, rtol=1e-5)
