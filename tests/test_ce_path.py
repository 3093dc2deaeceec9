"""The hard-label cross-entropy path every training step runs
(``nn/functional/loss.py``: two streaming reductions over the logits and one
gather, statistics in float32) against a float64 NumPy reference."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.nn.functional.loss import softmax_with_cross_entropy_raw


def _ref_nll(x, y):
    x = np.asarray(x, np.float64)
    m = x.max(-1, keepdims=True)
    lse = (m + np.log(np.exp(x - m).sum(-1, keepdims=True)))[:, 0]
    return lse - x[np.arange(len(y)), y]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forward_parity(dtype):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(32, 384).astype(np.float32) * 4, dtype)
    y = rng.randint(0, 384, 32).astype(np.int32)
    nll = F.cross_entropy(paddle.to_tensor(x), paddle.to_tensor(y),
                          reduction="none")
    assert nll.numpy().dtype == np.float32      # whatever the logits' type
    want = _ref_nll(np.asarray(x, np.float32), y)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(nll.numpy(), want, atol=tol, rtol=tol)


def test_grad_parity():
    rng = np.random.RandomState(1)
    x = rng.randn(16, 256).astype(np.float32) * 3
    y = rng.randint(0, 256, 16).astype(np.int32)
    gvec = rng.randn(16).astype(np.float32)
    got = jax.grad(lambda a: jnp.sum(
        softmax_with_cross_entropy_raw(a, jnp.asarray(y)) * gvec))(
            jnp.asarray(x))
    # d nll_i / d x_ij = softmax(x_i)_j - [j == y_i]
    x64 = x.astype(np.float64)
    p = np.exp(x64 - x64.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    p[np.arange(16), y] -= 1.0
    np.testing.assert_allclose(np.asarray(got), p * gvec[:, None],
                               atol=1e-5, rtol=1e-4)


def test_cross_entropy_matches_reference():
    """Int64 labels over leading axes, one position ignored, and the mean
    over the positions that count."""
    rng = np.random.RandomState(2)
    logits = rng.randn(4, 8, 128).astype(np.float32)
    labels = rng.randint(0, 128, (4, 8)).astype(np.int64)
    labels[1, 3] = -100
    out = F.cross_entropy(paddle.to_tensor(logits), paddle.to_tensor(labels),
                          reduction="none")
    keep = labels.reshape(-1) != -100
    want = np.where(keep, _ref_nll(logits.reshape(-1, 128),
                                   np.where(keep, labels.reshape(-1), 0)),
                    0.0).reshape(4, 8)
    np.testing.assert_allclose(out.numpy(), want, atol=1e-4, rtol=1e-4)
    mean = F.cross_entropy(paddle.to_tensor(logits),
                           paddle.to_tensor(labels))
    np.testing.assert_allclose(float(mean.numpy()), want.sum() / keep.sum(),
                               rtol=1e-5)
