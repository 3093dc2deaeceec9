"""1F1B pipeline-parallel tests on the 8-device CPU mesh.

Done-criterion from round-1 review: PP loss AND grads == sequential loss on
the same stacked stages (reference semantics:
fleet/meta_parallel/pipeline_parallel.py:80 forward_backward_pipeline).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec

from paddle_tpu.distributed.pipeline import spmd_pipeline_1f1b


def _stage_fn(params, x):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + x  # residual keeps magnitudes stable


def _loss_fn(out, label):
    return jnp.mean((out - label) ** 2)


@pytest.mark.parametrize("num_stages,num_micro", [(4, 8), (8, 8), (2, 5)])
def test_1f1b_matches_sequential(num_stages, num_micro):
    devices = jax.devices()[:num_stages]
    mesh = Mesh(np.asarray(devices), ("pp",))
    d, mb = 16, 4
    rng = np.random.RandomState(0)
    params = {
        "w1": jnp.asarray(rng.randn(num_stages, d, d) * 0.3, jnp.float32),
        "b1": jnp.asarray(rng.randn(num_stages, d) * 0.1, jnp.float32),
        "w2": jnp.asarray(rng.randn(num_stages, d, d) * 0.3, jnp.float32),
    }
    x = jnp.asarray(rng.randn(num_micro, mb, d), jnp.float32)
    labels = jnp.asarray(rng.randn(num_micro, mb, d), jnp.float32)

    # ---- sequential reference -------------------------------------------
    def seq_loss(params, x, labels):
        def one_micro(i):
            h = x[i]
            for s in range(num_stages):
                slice_p = {k: v[s] for k, v in params.items()}
                h = _stage_fn(slice_p, h)
            return _loss_fn(h, labels[i])
        return sum(one_micro(i) for i in range(num_micro)) / num_micro

    ref_loss, ref_grads = jax.value_and_grad(seq_loss)(params, x, labels)

    # ---- 1F1B pipeline ----------------------------------------------------
    pspec = PartitionSpec("pp")
    pipe = shard_map(
        lambda p, x_, l_: spmd_pipeline_1f1b(
            _stage_fn, _loss_fn, p, x_, l_, num_stages, num_micro),
        mesh=mesh,
        in_specs=({"w1": pspec, "b1": pspec, "w2": pspec},
                  PartitionSpec(), PartitionSpec()),
        out_specs=(PartitionSpec(), {"w1": pspec, "b1": pspec, "w2": pspec}),
    )
    loss, grads = jax.jit(pipe)(params, x, labels)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for k in params:
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(ref_grads[k]),
                                   atol=1e-5, rtol=1e-4, err_msg=k)


def test_train_batch_microbatch_accumulation():
    """PipelineParallel.train_batch with accumulate_steps=4 must produce the
    same update as a single full-batch step (grad accumulation parity)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.pipeline import (PipelineLayer,
                                                 PipelineParallel)

    def build():
        paddle.seed(7)
        layers = [nn.Linear(8, 8), nn.Tanh(), nn.Linear(8, 8), nn.Tanh(),
                  nn.Linear(8, 8), nn.Linear(8, 4)]
        pl = PipelineLayer(layers, num_stages=3,
                           loss_fn=nn.MSELoss())
        return PipelineParallel(pl)

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(8, 8).astype(np.float32))
    y = paddle.to_tensor(rng.randn(8, 4).astype(np.float32))

    m1 = build()
    opt1 = paddle.optimizer.SGD(learning_rate=0.1,
                                parameters=m1.parameters())
    m1.accumulate_steps = 1
    l1 = m1.train_batch((x, y), opt1)

    m2 = build()
    opt2 = paddle.optimizer.SGD(learning_rate=0.1,
                                parameters=m2.parameters())
    m2.accumulate_steps = 4
    l2 = m2.train_batch((x, y), opt2)

    np.testing.assert_allclose(float(l1.numpy()), float(l2.numpy()),
                               rtol=1e-5)
    for p1, p2 in zip(m1.parameters(), m2.parameters()):
        np.testing.assert_allclose(np.asarray(p1.numpy()),
                                   np.asarray(p2.numpy()),
                                   atol=1e-6, rtol=1e-5)


def test_train_batch_rejects_indivisible_batch():
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.pipeline import (PipelineLayer,
                                                 PipelineParallel)

    pl = PipelineLayer([nn.Linear(4, 4)], num_stages=1,
                       loss_fn=nn.MSELoss())
    pp = PipelineParallel(pl)
    pp.accumulate_steps = 3
    x = paddle.to_tensor(np.zeros((8, 4), np.float32))
    with pytest.raises(ValueError):
        pp.train_batch((x, x), paddle.optimizer.SGD(
            learning_rate=0.1, parameters=pp.parameters()))
