"""AMP O2 master-weight tests (reference semantics:
python/paddle/optimizer/optimizer.py _multi_precision master params +
fluid/dygraph/amp/loss_scaler.py:40).

The failure mode being guarded: with bf16 params and lr*grad below the bf16
ULP (~0.8% at magnitude 1), updates round to zero and training silently
stalls.  The fp32 master copy must accumulate them.
"""
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn


def test_bf16_small_updates_accumulate_eager():
    p = paddle.to_tensor(np.ones((4, 4), np.float32))
    lin = nn.Linear(4, 4)
    lin.weight.set_value(paddle.to_tensor(np.ones((4, 4), np.float32)))
    lin.weight._array = lin.weight._array.astype(jnp.bfloat16)
    opt = paddle.optimizer.SGD(learning_rate=1e-4,
                               parameters=[lin.weight])
    for _ in range(100):
        # constant unit gradient
        lin.weight.grad = paddle.to_tensor(np.ones((4, 4), np.float32))
        opt.step()
    got = np.asarray(lin.weight._array.astype(jnp.float32))
    # 100 steps x 1e-4: each too small for a bf16 ULP at 1.0, but the
    # master accumulates to ~0.99
    np.testing.assert_allclose(got, 0.99, atol=5e-3)


def test_bf16_updates_vanish_without_master():
    lin = nn.Linear(4, 4)
    lin.weight.set_value(paddle.to_tensor(np.ones((4, 4), np.float32)))
    lin.weight._array = lin.weight._array.astype(jnp.bfloat16)
    opt = paddle.optimizer.SGD(learning_rate=1e-4, parameters=[lin.weight],
                               multi_precision=False)
    for _ in range(100):
        lin.weight.grad = paddle.to_tensor(np.ones((4, 4), np.float32))
        opt.step()
    got = np.asarray(lin.weight._array.astype(jnp.float32))
    # documents the hazard the master fixes: all updates rounded away
    np.testing.assert_allclose(got, 1.0)


def test_trainstep_o2_master_weights():
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    m = nn.Linear(8, 8, bias_attr=False)
    paddle.amp.decorate(m, level="O2", dtype="bfloat16")
    assert m.weight.dtype == paddle.bfloat16
    opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                 learning_rate=1e-4)
    step = TrainStep(m, lambda o, t: paddle.nn.functional.mse_loss(o, t),
                     opt)
    # O2 contract: the step state holds ONE fp32 master per bf16 param
    # (cast to bf16 inside the compiled step), so no separate "master"
    # slot exists — two copies would defeat donation aliasing (PERF.md)
    assert step._compute_dtypes  # bf16 params detected
    leaf = next(iter(step.opt_state["slots"].values()))
    assert "master" not in leaf
    assert next(iter(step.params.values())).dtype == jnp.float32
    x = paddle.to_tensor(np.random.RandomState(0).randn(16, 8)
                         .astype(np.float32))
    y = paddle.to_tensor(np.random.RandomState(1).randn(16, 8)
                         .astype(np.float32))
    l0 = float(step(x, y).numpy())
    for _ in range(120):
        loss = step(x, y)
    assert float(loss.numpy()) < l0  # tiny updates actually land
    # syncing back restores the model's bf16 params
    step.sync_to_model()
    assert m.weight.dtype == paddle.bfloat16


def test_amp_o2_keeps_gpt_layer_norms_fp32():
    """decorate(level='O2') casts a GPT's matrices and biases to bf16 and
    leaves every LayerNorm parameter f32 (reference keep_batch_norm_fp32)."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny())
    paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    sd = model.state_dict()
    norms = [k for k in sd if ".ln1." in k or ".ln2." in k or ".ln_f." in k]
    assert len(norms) == 2 * (2 * 2 + 1)
    assert all(sd[k].dtype == paddle.float32 for k in norms)
    assert all(sd[k].dtype == paddle.bfloat16 for k in sd if k not in norms)


def test_trainstep_state_dict_round_trip_under_o2():
    """The checkpoint contract of an AMP O2 step: ``state_dict`` speaks the
    model's own names with f32 masters, and a fresh step that loads it
    takes the same next step."""
    from paddle_tpu.jit import TrainStep

    def build():
        paddle.seed(7)
        m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 16),
                          nn.LayerNorm(16))
        paddle.amp.decorate(m, level="O2", dtype="bfloat16")
        opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                     learning_rate=1e-2, weight_decay=0.01)
        return m, opt

    x = jnp.asarray(np.random.RandomState(0).randn(4, 16).astype(np.float32))
    y = jnp.asarray(np.random.RandomState(1).randn(4, 16).astype(np.float32))
    loss_fn = lambda out, lab: ((out - lab) ** 2).mean()

    m, opt = build()
    step = TrainStep(m, loss_fn, opt)
    for _ in range(5):
        step(x, y)
    sd = step.state_dict()
    names = {n for n, _ in m.named_parameters()}
    assert set(sd["params"]) == names
    assert set(sd["opt_state"]["slots"]) == names
    assert all(v.dtype == jnp.float32 for v in sd["params"].values())

    m2, opt2 = build()
    fresh = TrainStep(m2, loss_fn, opt2)
    fresh.set_state_dict(sd)
    assert all(v.dtype == jnp.float32 for v in fresh.params.values())
    for k, v in sd["params"].items():
        np.testing.assert_array_equal(np.asarray(fresh.params[k]),
                                      np.asarray(v), err_msg=k)
    assert float(fresh(x, y).numpy()) == float(step(x, y).numpy())


def test_adamw_bf16_moment_dtype():
    """Opt-in reduced-precision optimizer state (round 5,
    Adam/AdamW(moment_dtype='bfloat16')): moments STORED bf16, update math
    f32 — the training trajectory stays close to the f32-state run, and
    the checkpoint round-trips the reduced dtypes."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.jit import TrainStep

    X = paddle.to_tensor(
        np.random.RandomState(0).rand(32, 16).astype("float32"))
    Y = paddle.to_tensor(
        np.random.RandomState(1).rand(32, 4).astype("float32"))

    def run(mdt):
        paddle.seed(7)
        m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
        opt = paddle.optimizer.AdamW(parameters=m.parameters(),
                                     learning_rate=1e-2,
                                     moment_dtype=mdt)
        step = TrainStep(m, nn.MSELoss(), opt)
        losses = [float(step(X, Y).numpy()) for _ in range(20)]
        return losses, step

    losses32, _ = run(None)
    losses16, step16 = run("bfloat16")
    assert losses16[-1] < losses16[0]
    # bf16 state perturbs the trajectory only mildly at this scale
    np.testing.assert_allclose(losses16, losses32, rtol=0.15, atol=0.02)

    sd = step16.state_dict()
    slots = sd["opt_state"]["slots"]
    k = next(iter(slots))
    assert str(slots[k]["moment1"].dtype) == "bfloat16"
    assert str(slots[k]["moment2"].dtype) == "bfloat16"
    # restore keeps the reduced dtypes (placement preserves old dtype)
    step16.set_state_dict(sd)
    k2 = next(iter(step16.opt_state["slots"]))
    assert str(step16.opt_state["slots"][k2]["moment1"].dtype) == "bfloat16"
