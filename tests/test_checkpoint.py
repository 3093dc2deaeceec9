"""Checkpoint / auto-resume tests.

Contract (reference: fluid/incubate/checkpoint/auto_checkpoint.py:265
TrainEpochRange — snapshot, restore, fast-forward the data stream): a run
killed mid-training and restarted must reproduce the EXACT loss trajectory
of an uninterrupted run.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.incubate.checkpoint import (CheckpointManager,
                                            ResumableIterator)


def test_manager_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    state = {"w": paddle.to_tensor([[1.0, 2.0], [3.0, 4.0]]),
             "step": 7, "lr": 0.5, "nested": {"b": np.arange(3)}}
    mgr.save(3, state)
    assert mgr.latest_step() == 3
    out = mgr.restore()
    np.testing.assert_allclose(out["w"], [[1.0, 2.0], [3.0, 4.0]])
    assert out["step"] == 7 and out["lr"] == 0.5
    np.testing.assert_array_equal(out["nested"]["b"], np.arange(3))


def test_manager_async_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2, async_save=True)
    for s in range(5):
        mgr.save(s, {"v": np.full((4,), s)})
    mgr.wait()
    assert mgr.all_steps() == [3, 4]
    out = mgr.restore()
    np.testing.assert_array_equal(out["v"], np.full((4,), 4))


def test_manager_ignores_incomplete(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"v": 1})
    # a torn checkpoint (no DONE marker) must not be eligible
    os.makedirs(os.path.join(str(tmp_path), "ckpt-2"))
    with open(os.path.join(str(tmp_path), "ckpt-2", "host-0.ckpt"),
              "wb") as f:
        f.write(b"garbage")
    assert mgr.latest_step() == 1


def test_sharded_leaf_roundtrip(tmp_path):
    """A mesh-sharded array is saved as shards and reassembled, then placed
    back onto the template's sharding."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(-1), ("x",))
    arr = jax.device_put(np.arange(16.0).reshape(8, 2),
                         NamedSharding(mesh, PartitionSpec("x", None)))
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(0, {"p": arr})
    out = mgr.restore(template={"p": arr})
    np.testing.assert_allclose(np.asarray(out["p"]),
                               np.arange(16.0).reshape(8, 2))
    assert out["p"].sharding == arr.sharding


def test_resumable_iterator_fast_forward():
    from paddle_tpu.io import DataLoader, TensorDataset
    xs = paddle.to_tensor(np.arange(12, dtype=np.float32).reshape(12, 1))
    loader = DataLoader(TensorDataset([xs]), batch_size=2, shuffle=False)
    it = ResumableIterator(loader)
    seen = []
    for i, (b,) in enumerate(it):
        seen.append(float(b.numpy()[0, 0]))
        if i == 2:
            cursor = it.state_dict()   # consumed 3 batches
    # fresh process sim: new iterator, restore cursor, resume epoch
    it2 = ResumableIterator(loader)
    it2.set_state_dict(cursor)
    resumed = [float(b.numpy()[0, 0]) for (b,) in it2]
    assert seen[:3] + resumed == seen  # identical stream


_TRAIN_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.incubate.checkpoint import CheckpointManager

    ckdir, die_at = sys.argv[1], int(sys.argv[2])
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(4, 16), nn.ReLU(), nn.Linear(16, 1))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())
    step = TrainStep(net, nn.functional.mse_loss, opt)
    mgr = CheckpointManager(ckdir, max_to_keep=2)

    rng = np.random.RandomState(0)
    data = [(rng.randn(8, 4).astype('float32'),
             rng.randn(8, 1).astype('float32')) for _ in range(10)]

    start = 0
    if mgr.latest_step() is not None:
        payload = mgr.restore(template={"train": step.state_dict(),
                                        "rng": None, "i": None})
        step.set_state_dict(payload["train"])
        paddle.set_rng_state(payload["rng"])
        start = payload["i"] + 1
    losses = []
    for i in range(start, 10):
        loss = step(paddle.to_tensor(data[i][0]), paddle.to_tensor(data[i][1]))
        losses.append(float(loss))
        mgr.save(i, {"train": step.state_dict(),
                     "rng": paddle.get_rng_state(), "i": i})
        if i == die_at:
            mgr.wait()
            os._exit(17)   # simulated crash: no cleanup, mid-run
    mgr.wait()
    print("LOSSES", ",".join("%.10f" % l for l in losses))
""")


@pytest.mark.slow
def test_kill_and_resume_identical_trajectory(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"

    def run(ckdir, die_at):
        return subprocess.run(
            [sys.executable, "-c", _TRAIN_SCRIPT, ckdir, str(die_at)],
            capture_output=True, text=True, timeout=600, cwd="/root/repo",
            env=env)

    # uninterrupted reference run
    ref = run(os.path.join(str(tmp_path), "ref"), -1)
    assert ref.returncode == 0, ref.stderr[-2000:]
    ref_losses = ref.stdout.split("LOSSES ")[1].strip().split(",")

    # crash after step 4, then resume
    ckdir = os.path.join(str(tmp_path), "crashy")
    crashed = run(ckdir, 4)
    assert crashed.returncode == 17, (crashed.returncode,
                                      crashed.stderr[-2000:])
    resumed = run(ckdir, -1)
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    resumed_losses = resumed.stdout.split("LOSSES ")[1].strip().split(",")
    # steps 5..9 of the resumed run must equal the reference exactly
    assert resumed_losses == ref_losses[5:]
