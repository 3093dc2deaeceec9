"""Multi-process eager DataParallel (reference semantics: parallel.py:413
DataParallel + EagerReducer grad allreduce across processes).

Spawns 2 real jax processes over localhost (jax.distributed rendezvous via
the PADDLE_MASTER contract), each computing different per-rank gradients;
apply_collective_grads must leave BOTH ranks holding the cross-process
mean, and sync_params_buffers must broadcast rank 0's weights."""
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.slow

_WORKER = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    # rendezvous BEFORE anything can touch the XLA backend
    import jax
    jax.distributed.initialize(
        coordinator_address=os.environ["PADDLE_MASTER"],
        num_processes=int(os.environ["PADDLE_TRAINERS_NUM"]),
        process_id=int(os.environ["PADDLE_TRAINER_ID"]))
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.distributed as dist

    env = dist.init_parallel_env()
    rank = dist.get_rank()
    assert dist.get_world_size() == 2, dist.get_world_size()

    paddle.seed(100 + rank)             # DIFFERENT init per rank
    net = nn.Linear(4, 2)
    model = paddle.DataParallel(net)    # broadcasts rank 0's params

    w0 = net.weight.numpy().copy()

    # different data per rank -> different local grads
    x = paddle.to_tensor(np.full((2, 4), float(rank + 1), np.float32))
    loss = model(x).sum()
    loss.backward()
    local_grad = net.weight.grad.numpy().copy()
    model.apply_collective_grads()
    synced = net.weight.grad.numpy()

    out = os.path.join(os.environ["DP_TEST_DIR"], f"rank{rank}.npz")
    np.savez(out, w0=w0, local=local_grad, synced=synced)
    print("RANK", rank, "OK")
""")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_dp_grad_sync(tmp_path):
    script = os.path.join(str(tmp_path), "worker.py")
    with open(script, "w") as f:
        f.write(_WORKER)
    port = _free_port()
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + (
            ":" + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""),
        "PADDLE_MASTER": f"127.0.0.1:{port}",
        "DP_TEST_DIR": str(tmp_path),
    })
    from paddle_tpu.distributed.launch_main import Launcher
    old = dict(os.environ)
    os.environ.clear()
    os.environ.update(env)
    try:
        launcher = Launcher(nproc_per_node=2,
                            log_dir=os.path.join(str(tmp_path), "log"))
        rc = launcher.run([sys.executable, script])
    finally:
        os.environ.clear()
        os.environ.update(old)
    logs = "\n".join(
        open(os.path.join(str(tmp_path), "log", f"workerlog.{r}")).read()
        for r in (0, 1))
    assert rc == 0, logs[-3000:]

    r0 = np.load(os.path.join(str(tmp_path), "rank0.npz"))
    r1 = np.load(os.path.join(str(tmp_path), "rank1.npz"))
    # params were broadcast from rank 0 before the forward
    np.testing.assert_allclose(r0["w0"], r1["w0"])
    # local grads differ (different data), synced grads are the mean and
    # identical across ranks
    assert not np.allclose(r0["local"], r1["local"])
    want = (r0["local"] + r1["local"]) / 2.0
    np.testing.assert_allclose(r0["synced"], want, rtol=1e-6)
    np.testing.assert_allclose(r1["synced"], want, rtol=1e-6)
