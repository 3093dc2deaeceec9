"""Layer tests (reference model: unittests/test_layers.py and per-layer
tests)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def test_linear_shapes_and_grad():
    layer = nn.Linear(8, 4)
    x = paddle.randn([2, 8])
    out = layer(x)
    assert out.shape == [2, 4]
    out.sum().backward()
    assert layer.weight.grad is not None
    assert layer.weight.grad.shape == [8, 4]
    assert layer.bias.grad.shape == [4]


def test_conv2d_parity_with_torch():
    torch = pytest.importorskip("torch")
    x = np.random.rand(2, 3, 8, 8).astype(np.float32)
    w = np.random.rand(5, 3, 3, 3).astype(np.float32)
    b = np.random.rand(5).astype(np.float32)
    ours = F.conv2d(paddle.to_tensor(x), paddle.to_tensor(w),
                    paddle.to_tensor(b), stride=2, padding=1).numpy()
    theirs = torch.nn.functional.conv2d(
        torch.tensor(x), torch.tensor(w), torch.tensor(b), stride=2,
        padding=1).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-5)


def test_conv2d_groups_dilation():
    torch = pytest.importorskip("torch")
    x = np.random.rand(1, 4, 10, 10).astype(np.float32)
    w = np.random.rand(8, 2, 3, 3).astype(np.float32)
    ours = F.conv2d(paddle.to_tensor(x), paddle.to_tensor(w), None,
                    padding=2, dilation=2, groups=2).numpy()
    theirs = torch.nn.functional.conv2d(
        torch.tensor(x), torch.tensor(w), None, padding=2, dilation=2,
        groups=2).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-5)


def test_conv2d_transpose_parity():
    torch = pytest.importorskip("torch")
    x = np.random.rand(2, 4, 5, 5).astype(np.float32)
    w = np.random.rand(4, 6, 3, 3).astype(np.float32)
    ours = F.conv2d_transpose(paddle.to_tensor(x), paddle.to_tensor(w),
                              stride=2, padding=1, output_padding=1).numpy()
    theirs = torch.nn.functional.conv_transpose2d(
        torch.tensor(x), torch.tensor(w), stride=2, padding=1,
        output_padding=1).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-5)


def test_pools_parity():
    torch = pytest.importorskip("torch")
    x = np.random.rand(2, 3, 8, 8).astype(np.float32)
    ours = F.max_pool2d(paddle.to_tensor(x), 2, 2).numpy()
    theirs = torch.nn.functional.max_pool2d(torch.tensor(x), 2, 2).numpy()
    np.testing.assert_allclose(ours, theirs)
    ours = F.avg_pool2d(paddle.to_tensor(x), 3, 2, 1).numpy()
    theirs = torch.nn.functional.avg_pool2d(
        torch.tensor(x), 3, 2, 1, count_include_pad=False).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-6)
    ours = F.adaptive_avg_pool2d(paddle.to_tensor(x), (3, 5)).numpy()
    theirs = torch.nn.functional.adaptive_avg_pool2d(
        torch.tensor(x), (3, 5)).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-6)


def test_batch_norm_train_eval():
    bn = nn.BatchNorm2D(4)
    x = paddle.randn([8, 4, 5, 5])
    bn.train()
    out = bn(x)
    # batch-normalized output: ~zero mean, ~unit var per channel
    m = out.numpy().mean(axis=(0, 2, 3))
    np.testing.assert_allclose(m, np.zeros(4), atol=1e-5)
    # running stats moved off init
    assert not np.allclose(bn._mean.numpy(), np.zeros(4))
    bn.eval()
    out2 = bn(x)
    assert out2.shape == out.shape


def test_layer_norm_parity():
    torch = pytest.importorskip("torch")
    x = np.random.rand(2, 3, 8).astype(np.float32)
    w = np.random.rand(8).astype(np.float32)
    b = np.random.rand(8).astype(np.float32)
    ours = F.layer_norm(paddle.to_tensor(x), [8], paddle.to_tensor(w),
                        paddle.to_tensor(b)).numpy()
    theirs = torch.nn.functional.layer_norm(
        torch.tensor(x), [8], torch.tensor(w), torch.tensor(b)).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-5)


def test_embedding_and_padding_idx():
    emb = nn.Embedding(10, 4, padding_idx=0)
    ids = paddle.to_tensor(np.array([[1, 0, 3]]))
    out = emb(ids)
    assert out.shape == [1, 3, 4]
    np.testing.assert_allclose(out.numpy()[0, 1], np.zeros(4))


def test_dropout_train_eval():
    d = nn.Dropout(0.5)
    x = paddle.ones([1000])
    d.train()
    y = d(x)
    kept = (y.numpy() != 0).mean()
    assert 0.3 < kept < 0.7
    np.testing.assert_allclose(y.numpy()[y.numpy() != 0], 2.0)  # upscaled
    d.eval()
    np.testing.assert_allclose(d(x).numpy(), x.numpy())


def test_cross_entropy_parity():
    torch = pytest.importorskip("torch")
    logits = np.random.rand(8, 5).astype(np.float32)
    labels = np.random.randint(0, 5, 8)
    ours = F.cross_entropy(paddle.to_tensor(logits),
                           paddle.to_tensor(labels)).numpy()
    theirs = torch.nn.functional.cross_entropy(
        torch.tensor(logits), torch.tensor(labels)).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-6)


def test_cross_entropy_ignore_and_smoothing():
    torch = pytest.importorskip("torch")
    logits = np.random.rand(8, 5).astype(np.float32)
    labels = np.random.randint(0, 5, 8)
    labels[0] = -100
    ours = F.cross_entropy(paddle.to_tensor(logits), paddle.to_tensor(labels),
                           ignore_index=-100).numpy()
    theirs = torch.nn.functional.cross_entropy(
        torch.tensor(logits), torch.tensor(labels), ignore_index=-100).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-6)
    labels2 = np.random.randint(0, 5, 8)
    ours = F.cross_entropy(paddle.to_tensor(logits),
                           paddle.to_tensor(labels2),
                           label_smoothing=0.1).numpy()
    theirs = torch.nn.functional.cross_entropy(
        torch.tensor(logits), torch.tensor(labels2),
        label_smoothing=0.1).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-6)


def test_losses_parity():
    torch = pytest.importorskip("torch")
    a = np.random.rand(4, 3).astype(np.float32)
    b = np.random.rand(4, 3).astype(np.float32)
    np.testing.assert_allclose(
        F.mse_loss(paddle.to_tensor(a), paddle.to_tensor(b)).numpy(),
        torch.nn.functional.mse_loss(torch.tensor(a), torch.tensor(b)).numpy(),
        rtol=1e-4, atol=1e-6)
    logit = np.random.randn(4, 3).astype(np.float32)
    lbl = (np.random.rand(4, 3) > 0.5).astype(np.float32)
    np.testing.assert_allclose(
        F.binary_cross_entropy_with_logits(
            paddle.to_tensor(logit), paddle.to_tensor(lbl)).numpy(),
        torch.nn.functional.binary_cross_entropy_with_logits(
            torch.tensor(logit), torch.tensor(lbl)).numpy(), rtol=1e-4, atol=1e-6)


def test_activations_parity():
    torch = pytest.importorskip("torch")
    x = np.random.randn(4, 8).astype(np.float32)
    pairs = [
        (F.relu, torch.nn.functional.relu),
        (F.gelu, lambda t: torch.nn.functional.gelu(t)),
        (F.silu, torch.nn.functional.silu),
        (F.softmax, lambda t: torch.nn.functional.softmax(t, -1)),
        (F.log_softmax, lambda t: torch.nn.functional.log_softmax(t, -1)),
        (F.leaky_relu, torch.nn.functional.leaky_relu),
        (F.elu, torch.nn.functional.elu),
        (F.softplus, torch.nn.functional.softplus),
        (F.hardswish, torch.nn.functional.hardswish),
    ]
    for ours_fn, theirs_fn in pairs:
        np.testing.assert_allclose(
            ours_fn(paddle.to_tensor(x)).numpy(),
            theirs_fn(torch.tensor(x)).numpy(), rtol=1e-3, atol=1e-4)


def test_state_dict_roundtrip():
    m1 = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    m2 = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    sd = m1.state_dict()
    assert len(sd) == 4
    m2.set_state_dict(sd)
    x = paddle.randn([3, 4])
    np.testing.assert_allclose(m1(x).numpy(), m2(x).numpy(), rtol=1e-6)


def test_hooks():
    layer = nn.Linear(2, 2)
    calls = []
    h1 = layer.register_forward_pre_hook(
        lambda l, inp: calls.append("pre"))
    h2 = layer.register_forward_post_hook(
        lambda l, inp, out: calls.append("post"))
    layer(paddle.randn([1, 2]))
    assert calls == ["pre", "post"]
    h1.remove()
    h2.remove()
    layer(paddle.randn([1, 2]))
    assert calls == ["pre", "post"]


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_mha_and_transformer_encoder():
    mha = nn.MultiHeadAttention(16, 4, dropout=0.0)
    x = paddle.randn([2, 6, 16])
    out = mha(x)
    assert out.shape == [2, 6, 16]
    enc_layer = nn.TransformerEncoderLayer(16, 4, 32, dropout=0.0)
    enc = nn.TransformerEncoder(enc_layer, 2)
    out = enc(x)
    assert out.shape == [2, 6, 16]
    out.sum().backward()
    assert enc.layers[0].linear1.weight.grad is not None
    # distinct layers must not share parameters
    p0 = enc.layers[0].linear1.weight
    p1 = enc.layers[1].linear1.weight
    assert p0 is not p1


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_lstm_and_gru():
    lstm = nn.LSTM(8, 16, num_layers=2)
    x = paddle.randn([4, 5, 8])
    out, (h, c) = lstm(x)
    assert out.shape == [4, 5, 16]
    assert h.shape == [2, 4, 16]
    assert c.shape == [2, 4, 16]
    out.sum().backward()
    assert lstm.weight_ih_l0.grad is not None

    gru = nn.GRU(8, 16, direction="bidirect")
    out, h = gru(x)
    assert out.shape == [4, 5, 32]
    assert h.shape == [2, 4, 16]


def test_sequential_and_layerlist():
    seq = nn.Sequential(nn.Linear(2, 3), nn.Linear(3, 4))
    assert len(seq) == 2
    assert len(seq.parameters()) == 4
    ll = nn.LayerList([nn.Linear(2, 2) for _ in range(3)])
    assert len(list(ll)) == 3
    ll.append(nn.Linear(2, 2))
    assert len(ll) == 4


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_gpt_incremental_decode_matches_full_forward():
    """KV-cache decode (GPTForCausalLM cache path): feeding tokens one at a
    time through gen_cache must reproduce the full-context logits at every
    position (the inference decode contract; reference MultiHeadAttention
    Cache semantics)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(5)
    cfg = GPTConfig.tiny()
    m = GPTForCausalLM(cfg)
    m.eval()
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size,
                                           (2, 12)).astype(np.int32)
    x = paddle.to_tensor(ids)
    full_logits = m(x).numpy()                      # (2, 12, V)

    cache = m.gen_cache(batch_size=2, dtype="float32")
    step_logits = []
    for t in range(ids.shape[1]):
        tok = paddle.to_tensor(ids[:, t:t + 1])
        logits, cache = m(tok, cache=cache)
        step_logits.append(np.asarray(logits.numpy())[:, 0, :])
    inc = np.stack(step_logits, axis=1)             # (2, 12, V)
    np.testing.assert_allclose(inc, np.asarray(full_logits),
                               rtol=2e-4, atol=2e-4)
    # greedy continuation agrees with the full-context argmax
    assert np.array_equal(inc[:, -1, :].argmax(-1),
                          np.asarray(full_logits)[:, -1, :].argmax(-1))
