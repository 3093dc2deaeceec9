"""Auto-parallel Engine + sequence_mask + check_nan_inf hook tests."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed import mesh as _mesh


@pytest.fixture(autouse=True)
def _mesh_as_found():
    """``Engine.prepare`` and ``init_mesh`` install a global mesh, as their
    users ask of them; a test puts back what it found."""
    before = _mesh.get_mesh()
    yield
    _mesh.set_mesh(before)


def test_engine_fit_evaluate_predict():
    from paddle_tpu.distributed.auto_parallel import Engine, ProcessMesh
    from paddle_tpu.io import TensorDataset

    paddle.seed(0)
    rng = np.random.RandomState(0)
    xs = paddle.to_tensor(rng.randn(64, 8).astype(np.float32))
    w = rng.randn(8, 1).astype(np.float32)
    ys = paddle.to_tensor(rng.randn(64, 8).astype(np.float32) @ w)
    ds = TensorDataset([xs, ys])

    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    engine = Engine(model=model, loss=nn.functional.mse_loss, optimizer=opt)
    pm = ProcessMesh(shape=[4, 2], dim_names=["dp", "mp"])
    engine.prepare(process_mesh=pm)
    hist = engine.fit(ds, epochs=3, batch_size=16, verbose=0)
    assert len(hist) == 3
    assert hist[-1]["loss"] < hist[0]["loss"]

    result = engine.evaluate(ds, batch_size=16)
    assert result["loss"] == pytest.approx(hist[-1]["loss"], rel=1.0)

    outs = engine.predict(ds, batch_size=16)
    assert len(outs) == 4 and tuple(outs[0].shape) == (16, 1)

    cost = engine.cost()
    assert cost["mesh"] == {"dp": 4, "mp": 2}


def test_engine_params_sharded_on_mesh():
    from paddle_tpu.distributed.auto_parallel import Engine
    model = nn.Linear(8, 8)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    engine = Engine(model=model, loss=nn.functional.mse_loss, optimizer=opt)
    engine.prepare(mesh_axes={"dp": 8})
    # parameters are placed on the mesh (replicated by default)
    sh = model.weight._array.sharding
    assert getattr(sh, "mesh", None) is not None


def test_shard_op_constrains():
    import jax
    from paddle_tpu.distributed.auto_parallel import shard_op
    _mesh.init_mesh({"dp": 8})

    def matmul(a, b):
        return a @ b

    f = shard_op(matmul, in_shard_specs=[("dp", None), None],
                 out_shard_specs=[("dp", None)])

    @jax.jit
    def run(a, b):
        return f(a, b)

    out = run(np.ones((8, 4), np.float32), np.ones((4, 4), np.float32))
    np.testing.assert_allclose(np.asarray(out), 4.0)


def test_sequence_mask():
    lens = paddle.to_tensor(np.array([1, 3, 0], np.int64))
    m = nn.functional.sequence_mask(lens, maxlen=4)
    want = np.array([[1, 0, 0, 0], [1, 1, 1, 0], [0, 0, 0, 0]], np.int64)
    np.testing.assert_array_equal(m.numpy(), want)
    # maxlen inferred from data
    m2 = nn.functional.sequence_mask(lens)
    assert m2.shape[-1] == 3
    # float dtype
    mf = nn.functional.sequence_mask(lens, maxlen=2, dtype="float32")
    assert mf.numpy().dtype == np.float32


def test_check_nan_inf_flag():
    paddle.set_flags({"FLAGS_check_nan_inf": True})
    try:
        a = paddle.to_tensor([1.0, 0.0])
        with pytest.raises(FloatingPointError, match="divide"):
            _ = a / paddle.to_tensor([1.0, 0.0])
        # finite ops pass through
        out = a + 1.0
        assert float(out.numpy()[0]) == 2.0
    finally:
        paddle.set_flags({"FLAGS_check_nan_inf": False})
    # disabled: no error (0/0 -> nan passes straight through)
    bad = a / paddle.to_tensor([1.0, 0.0])
    assert np.isnan(bad.numpy()[1])


def test_init_hybrid_mesh():
    """DCN axes outermost, ICI axes inner; a dp x mp step compiles on it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed.mesh import init_hybrid_mesh

    mesh = init_hybrid_mesh({"dp": 2}, {"mp": 4})
    assert mesh.axis_names == ("dp", "mp")
    assert mesh.devices.shape == (2, 4)
    with pytest.raises(ValueError):
        init_hybrid_mesh({"dp": 2}, {"dp": 4})

    x = jax.device_put(np.ones((8, 16), np.float32),
                       NamedSharding(mesh, P("dp", "mp")))
    w = jax.device_put(np.ones((16, 16), np.float32),
                       NamedSharding(mesh, P("mp", None)))
    out = jax.jit(lambda a, b: a @ b)(x, w)
    np.testing.assert_allclose(np.asarray(out), 16.0)
