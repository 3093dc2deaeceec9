"""Executable inference artifact tests.

Contract under test (reference: paddle/fluid/inference/api/analysis_predictor.h:90
load-and-run without the model-building code; python/paddle/static/io.py:433
save_inference_model): the exported artifact must run in a FRESH process with
only paddle_tpu installed — no access to the original Layer class.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.static import InputSpec, load_inference_model, save_inference_model


class SmallNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 4)

    def forward(self, x):
        return self.fc2(nn.functional.relu(self.fc1(x)))


def _export(tmp_path):
    net = SmallNet()
    net.eval()
    x = paddle.randn([3, 8])
    want = net(x).numpy()
    prefix = os.path.join(str(tmp_path), "model")
    save_inference_model(prefix, model=net,
                         input_spec=[InputSpec([3, 8], "float32")])
    return prefix, x.numpy(), want


def test_save_then_load_without_class(tmp_path):
    prefix, x, want = _export(tmp_path)
    # a module + params + meta + stablehlo text all exist
    for suffix in (".pdmodel", ".pdiparams", ".pdmodel.meta",
                   ".stablehlo.mlir"):
        assert os.path.exists(prefix + suffix), suffix
    predictor = load_inference_model(prefix)  # NOTE: no model class passed
    got = predictor(x)
    np.testing.assert_allclose(got.numpy(), want, rtol=1e-5, atol=1e-5)


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_load_in_fresh_process(tmp_path):
    prefix, x, want = _export(tmp_path)
    np.save(os.path.join(str(tmp_path), "x.npy"), x)
    np.save(os.path.join(str(tmp_path), "want.npy"), want)
    script = textwrap.dedent("""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        import numpy as np
        from paddle_tpu.static import load_inference_model
        prefix = sys.argv[1]
        x = np.load(os.path.join(os.path.dirname(prefix), "x.npy"))
        want = np.load(os.path.join(os.path.dirname(prefix), "want.npy"))
        predictor = load_inference_model(prefix)
        got = predictor(x)
        np.testing.assert_allclose(got.numpy(), want, rtol=1e-5, atol=1e-5)
        print("FRESH_PROCESS_OK")
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", script, prefix],
                       capture_output=True, text=True, timeout=300,
                       cwd="/root/repo", env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "FRESH_PROCESS_OK" in r.stdout


def test_jit_save_load_roundtrip(tmp_path):
    net = SmallNet()
    net.eval()
    x = paddle.randn([2, 8])
    want = net(x).numpy()
    prefix = os.path.join(str(tmp_path), "jit_model")
    paddle.jit.save(net, prefix, input_spec=[InputSpec([2, 8], "float32")])
    loaded = paddle.jit.load(prefix)
    got = loaded(x)
    np.testing.assert_allclose(got.numpy(), want, rtol=1e-5, atol=1e-5)


def test_gpt_jit_save_load_parity(tmp_path):
    """The GPT model exports through the StableHLO artifact path and the
    loaded program (no class) returns the eager model's logits."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    prefix = os.path.join(str(tmp_path), "gpt")
    paddle.jit.save(m, prefix, input_spec=[InputSpec([1, 8], "int32", "ids")])
    loaded = paddle.jit.load(prefix)
    ids = paddle.to_tensor(
        np.random.default_rng(6).integers(0, 512, (1, 8)).astype("int32"))
    got = loaded(ids)
    got = got[0] if isinstance(got, (list, tuple)) else got
    np.testing.assert_allclose(got.numpy(), m(ids).numpy(),
                               rtol=1e-4, atol=1e-5)


def test_jit_save_needs_spec(tmp_path):
    with pytest.raises(ValueError):
        paddle.jit.save(SmallNet(), os.path.join(str(tmp_path), "m"))


def test_static_nn_cond():
    from paddle_tpu.static import nn as snn
    a = paddle.to_tensor(2.0)
    out = snn.cond(a > 1.0, lambda: a * 2, lambda: a - 1)
    assert float(out) == 4.0
    out = snn.cond(a > 3.0, lambda: a * 2, lambda: a - 1)
    assert float(out) == 1.0


def test_static_nn_while_loop():
    from paddle_tpu.static import nn as snn
    i = paddle.to_tensor(0)
    s = paddle.to_tensor(0)
    i2, s2 = snn.while_loop(lambda i, s: i < 5,
                            lambda i, s: (i + 1, s + i), [i, s])
    assert int(i2) == 5 and int(s2) == 10


def test_static_nn_switch_case():
    from paddle_tpu.static import nn as snn
    idx = paddle.to_tensor(1)
    out = snn.switch_case(idx, {0: lambda: paddle.to_tensor(10.0),
                                1: lambda: paddle.to_tensor(20.0)},
                          default=lambda: paddle.to_tensor(-1.0))
    assert float(out) == 20.0
    out = snn.switch_case(paddle.to_tensor(7),
                          {0: lambda: paddle.to_tensor(10.0),
                           1: lambda: paddle.to_tensor(20.0)},
                          default=lambda: paddle.to_tensor(-1.0))
    assert float(out) == -1.0


def test_executor_run_triple_contract(tmp_path):
    """reference pattern: [prog, feeds, fetches] = load_inference_model(p, exe);
    exe.run(prog, feed=..., fetch_list=...)."""
    from paddle_tpu.static import Executor
    prefix, x, want = _export(tmp_path)
    exe = Executor()
    prog, feed_names, fetches = load_inference_model(prefix, executor=exe)
    assert feed_names == ["x0"]
    outs = exe.run(prog, feed={"x0": x}, fetch_list=fetches)
    np.testing.assert_allclose(outs[0], want, rtol=1e-5, atol=1e-5)


def test_executor_positional_and_model_paths(tmp_path):
    """Reference positional form load_inference_model(path, exe) and the
    model= re-trace path both work with Executor.run."""
    from paddle_tpu.static import Executor
    prefix, x, want = _export(tmp_path)
    exe = Executor()
    prog, feed_names, fetches = load_inference_model(prefix, exe)  # positional
    outs = exe.run(prog, feed={"x0": x}, fetch_list=fetches)
    np.testing.assert_allclose(outs[0], want, rtol=1e-5, atol=1e-5)
    # model in the second slot (old keywordless usage) still re-traces
    net = SmallNet()
    pred = load_inference_model(prefix, net)
    out = exe.run(pred, feed={"x0": x})
    assert out[0].shape == want.shape
