"""The program's side of the Qwen3-Next family: the chunked gated delta rule
against the token-by-token recurrence, the convolution without bias,
partial rotary, a zero-centred norm gain, the softmax router and gated
experts of the routed layer (both launches, the kernels in the
interpreter), and the roles and the counter of the new mixer in a compiled
training step."""
import gc
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                          Qwen3NextForCausalLM,
                                          Qwen3NextPretrainingCriterion)
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional import experts as FE
from paddle_tpu.nn.functional import linear_attn as FL
from paddle_tpu.nn.functional import ssm as FS
from paddle_tpu.nn.functional.attention import rotary_embedding_raw
from paddle_tpu.observability import scopes


def rule_inputs(seed, length, hk=2, hv=4, d=8, p=8, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.normal(0, 1.0, shape), jnp.float32)
    q = FL.l2_normalize_raw(arr(2, length, hk, d)) / math.sqrt(d)
    k = FL.l2_normalize_raw(arr(2, length, hk, d))
    v = arr(2, length, hv, p)
    g = -jnp.asarray(rng.uniform(0.001, 1.0, (2, length, hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (2, length, hv)), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.mark.parametrize("length,chunk", [
    (16, 16),     # one whole chunk
    (17, 16),     # one token across the boundary
    (40, 16),     # no multiple of the chunk
    (64, 16),     # four chunks: the state crosses three boundaries
    (7, 16),      # shorter than a chunk
    (40, 8),      # a chunk under the substitution's block
    (128, 64),    # the published chunk: two levels of block substitution
    (96, 32)])
def test_chunked_delta_rule_equals_the_recurrence(length, chunk):
    args = rule_inputs(length, length)
    want = FL.gated_delta_rule_recurrence_raw(*args)
    got = jax.jit(lambda *a: FL.gated_delta_rule_raw(*a, chunk))(*args)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("length,chunk", [(40, 16), (96, 32)])
def test_chunked_delta_rule_gradients_equal_the_recurrences(length, chunk):
    args = rule_inputs(3, length)
    probe = jnp.asarray(np.random.default_rng(9).normal(
        0, 1.0, args[2].shape), jnp.float32)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * probe)
    want = jax.grad(loss(FL.gated_delta_rule_recurrence_raw),
                    argnums=range(5))(*args)
    got = jax.jit(jax.grad(loss(
        lambda *a: FL.gated_delta_rule_raw(*a, chunk)),
        argnums=range(5)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-4)


def test_a_state_that_must_cross_chunks_is_carried():
    """One write at position 0 and a slow decay: every later chunk's output
    comes from the carried state alone."""
    q, k, v, g, beta = rule_inputs(1, 64)
    v = v.at[:, 1:].set(0.0)
    g = jnp.full_like(g, -0.01)
    out = jax.jit(lambda *a: FL.gated_delta_rule_raw(*a, 16))(q, k, v, g,
                                                              beta)
    assert float(jnp.abs(out[:, 48:]).mean()) > 1e-4
    np.testing.assert_allclose(
        out, FL.gated_delta_rule_recurrence_raw(q, k, v, g, beta),
        rtol=2e-4, atol=2e-6)


def test_key_heads_go_in_groups_past_the_state_budget(monkeypatch):
    """A row whose chunk states would pass the budget in the backward runs
    a group of key heads after the other: the same values and gradients."""
    assert FL._head_groups(512 * 2 ** 20, 16) == 2     # the 16k-token cell
    assert FL._head_groups(100, 16) == 1 and FL._head_groups(10 ** 12, 6) == 6
    args = rule_inputs(4, 48, hk=4, hv=8)
    probe = jnp.asarray(np.random.default_rng(1).normal(
        0, 1.0, args[2].shape), jnp.float32)
    run = lambda: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(FL.gated_delta_rule_raw(*a, 16) * probe),
        argnums=range(5)))(*args)
    whole = run()
    # 2 x 3 chunks x 8 heads x 8 x 8 x 4 B = 12,288 B of states
    monkeypatch.setattr(FL, "_STATE_HISTORY_BYTES", 4096)
    assert FL._head_groups(12288, 4) == 4
    grouped = run()
    np.testing.assert_allclose(grouped[0], whole[0], rtol=1e-5)
    for g, w in zip(grouped[1], whole[1]):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_the_correction_term_is_there():
    """The same key written twice with beta = 1: the second write replaces
    the first value (``u = v - S^T k``), it does not add to it."""
    k = jnp.zeros((1, 2, 1, 4)).at[:, :, :, 0].set(1.0)
    v = jnp.asarray([[[[1.0, 2.0]], [[5.0, -3.0]]]])         # (1, 2, 1, 2)
    out = jax.jit(lambda *a: FL.gated_delta_rule_raw(*a, 16))(
        k, k, v, jnp.zeros((1, 2, 1)), jnp.ones((1, 2, 1)))
    np.testing.assert_allclose(out[0, :, 0], v[0, :, 0], atol=1e-6)


@pytest.mark.parametrize("grads", [False, True])
def test_bf16_delta_rule_keeps_decay_inverse_and_state_in_float32(grads):
    args = rule_inputs(5, 64, dtype=jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    probe = jnp.asarray(np.random.default_rng(2).normal(
        0, 1.0, args[2].shape), jnp.float32)
    rel = lambda got, want: float(
        jnp.linalg.norm(got.astype(jnp.float32) - want)
        / jnp.linalg.norm(want))
    if not grads:
        got = jax.jit(lambda *a: FL.gated_delta_rule_raw(*a, 16))(*args)
        assert got.dtype == jnp.bfloat16
        assert rel(got, FL.gated_delta_rule_recurrence_raw(*exact)) < 0.02
        return
    loss = lambda f: lambda *a: jnp.sum(f(*a).astype(jnp.float32) * probe)
    want = jax.grad(loss(FL.gated_delta_rule_recurrence_raw),
                    argnums=range(5))(*exact)
    got = jax.jit(jax.grad(loss(lambda *a: FL.gated_delta_rule_raw(*a, 16)),
                           argnums=range(5)))(*args)
    for g, w in zip(got, want):
        assert rel(g, w) < 0.03


@pytest.mark.parametrize("n", [5, 16, 24, 64])
def test_unit_lower_inverse_is_the_inverse_even_of_a_stiff_matrix(n):
    """Entries near one (keys that repeat): the powers of such a matrix
    grow like binomials before they vanish, forward substitution does not
    care."""
    rng = np.random.default_rng(n)
    a = jnp.tril(jnp.asarray(rng.uniform(0.8, 1.0, (3, n, n)), jnp.float32),
                 -1)
    t = jax.jit(FL.unit_lower_inverse)(a)
    eye = jnp.eye(n)
    np.testing.assert_allclose(
        jnp.matmul(eye + a, t, precision="highest"),
        jnp.broadcast_to(eye, a.shape), atol=2e-4)
    probe = jnp.asarray(rng.normal(0, 1, a.shape), jnp.float32)
    got = jax.jit(jax.grad(
        lambda a: jnp.sum(FL.unit_lower_inverse(a) * probe)))(a)
    want = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(eye + a) * probe))(a)
    np.testing.assert_allclose(got, jnp.tril(want, -1), rtol=2e-3, atol=2e-3)


def test_causal_conv_without_bias():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (1, 12, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (4, 6)), jnp.float32)
    y = FS.causal_conv1d_raw(x, w)
    for t in (0, 2, 11):
        want = sum(w[j] * x[0, t - 3 + j] for j in range(4)
                   if t - 3 + j >= 0)
        np.testing.assert_allclose(y[0, t], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(FS.causal_conv1d_raw(x, w, silu=True),
                               jax.nn.silu(y), rtol=1e-6)
    np.testing.assert_allclose(
        y, FS.causal_conv1d_raw(x, w, jnp.zeros((6,))), rtol=1e-6)


# -- partial rotary, the zero-centred gain --------------------------------------

def test_partial_rotary_by_hand():
    """Head size 8, the first 4 lanes turned: lane 0 pairs with lane 2 at
    the angle position x 1, lane 1 with lane 3 at position x theta^-1/2;
    lanes 4-7 untouched."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (2, 5, 3, 8)), jnp.float32)
    theta = 100.0
    got = rotary_embedding_raw(x, 4, theta)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-7)  # position 0
    for pos in (1, 4):
        for lane, freq in ((0, 1.0), (1, theta ** -0.5)):
            c, s = math.cos(pos * freq), math.sin(pos * freq)
            a, b = x[:, pos, :, lane], x[:, pos, :, lane + 2]
            np.testing.assert_allclose(got[:, pos, :, lane], a * c - b * s,
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(got[:, pos, :, lane + 2],
                                       b * c + a * s, rtol=1e-5, atol=1e-6)
    # a turn: norms kept, and q.k depends on the distance alone
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    one = jnp.broadcast_to(x[:, :1], x.shape)
    turned = rotary_embedding_raw(one, 4, theta)
    dots = jnp.einsum("bqhd,bkhd->bhqk", turned, turned)
    np.testing.assert_allclose(dots[..., 1, 3], dots[..., 2, 4], rtol=1e-4)
    bf = rotary_embedding_raw(x.astype(jnp.bfloat16), 4, theta)
    assert bf.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="rotary_dim"):
        rotary_embedding_raw(x, 3)
    assert F.rotary_embedding(paddle.Tensor(x), 4, theta)._array.shape == \
        x.shape


def test_the_cells_rotary_turns_64_of_256_lanes():
    x = jnp.ones((1, 3, 2, 256), jnp.float32)
    got = rotary_embedding_raw(x, 64, 1e7)
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    assert float(jnp.abs(got[:, 1:, :, :64] - 1.0).max()) > 0.1


def test_a_zero_centred_gain_multiplies_by_one_plus_weight():
    from paddle_tpu.nn.layer.norm import RMSNorm
    x = paddle.Tensor(jnp.asarray(np.random.default_rng(0).normal(
        0, 2, (3, 16)), jnp.float32))
    plain, centred = RMSNorm(16), RMSNorm(16, zero_centered=True)
    assert float(jnp.abs(centred.weight._array).max()) == 0.0
    np.testing.assert_allclose(centred(x)._array, plain(x)._array, rtol=1e-6)
    centred.weight._array = jnp.full((16,), 0.5)
    np.testing.assert_allclose(centred(x)._array, 1.5 * plain(x)._array,
                               rtol=1e-6)
    assert centred.weight.keep_fp32


# -- the softmax router and gated experts --------------------------------------

def dense_gated(x, chosen, weights, held, w_gate, w_up, w_down):
    out = jnp.zeros_like(x)
    for j, e in enumerate(held):
        gate = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        out = out + gate[:, None] * (
            (jax.nn.silu(x @ w_gate[j]) * (x @ w_up[j])) @ w_down[j])
    return out


def gated_setup(seed, tokens=24, hidden=16, width=12, experts=16, held=8):
    rng = np.random.default_rng(seed)
    arr = lambda scale, *shape: jnp.asarray(rng.normal(0, scale, shape),
                                            jnp.float32)
    return (arr(1, tokens, hidden), arr(0.5, hidden, experts),
            arr(0.3, held, hidden, width), arr(0.3, held, hidden, width),
            arr(0.3, held, width, hidden))


def test_the_softmax_router_by_hand():
    x, router, *_ = gated_setup(0)
    chosen, weights = FE.route_softmax_raw(x, router, 4)
    probs = jax.nn.softmax(jnp.matmul(x, router, precision="highest"), -1)
    assert chosen.dtype == jnp.int32 and weights.dtype == jnp.float32
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    want = jnp.sort(probs, axis=-1)[:, ::-1][:, :4]
    np.testing.assert_allclose(
        weights, want / want.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_array_equal(
        jnp.take_along_axis(probs, chosen, -1), want)


@pytest.mark.parametrize("usual", [None, 8, 32, 10 ** 6])
@pytest.mark.parametrize("held", [(0, 1, 2, 3, 4, 5, 6, 7), (3, 9, 12),
                                  (15,)])
def test_no_token_is_dropped_by_gated_experts(monkeypatch, held, usual):
    """A router that sends every token to experts 0..5: the held ones among
    them get every token through either launch (8 rows never hold them, 32
    do for one held expert), their weights sum to one over the chosen, and
    the part is the dense computation's.  The overflow goes window by
    window, as at the 16k-token cell's size (``tests/test_nemotron_h.py``
    takes the worst case in one launch)."""
    monkeypatch.setattr(FE, "_ONE_LAUNCH_BYTES", 0)
    x, router, w_gate, w_up, w_down = gated_setup(1, held=len(held))
    k, experts = 6, 16
    # a constant feature lifts the logits of experts 0..5 far above the
    # others', for every token
    x = x.at[:, 0].set(1.0)
    router = router.at[0].set(jnp.where(jnp.arange(experts) < k, 40.0, 0.0))
    chosen, weights = FE.route_softmax_raw(x, router, k)
    assert set(np.asarray(chosen).ravel()) == set(range(k))
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-5)
    got = jax.jit(lambda *a: FE.held_experts_raw(*a[:5], usual, a[5]))(
        x, FE.local_ids(chosen, held, experts), weights, w_up, w_down, w_gate)
    want = dense_gated(x, chosen, weights, held, w_gate, w_up, w_down)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    if not sum(e < k for e in held):
        assert not bool(jnp.any(got))


@pytest.mark.parametrize("usual", [None, 8, 64])
def test_gated_expert_gradients_equal_the_dense_computation(monkeypatch,
                                                            usual):
    """Through the one launch, the usual one (64 rows hold the ~32 held
    assignments) and the fallback window by window (8 do not): both
    branches of the ``lax.cond``, forward and backward."""
    monkeypatch.setattr(FE, "_ONE_LAUNCH_BYTES", 0)
    x, router, w_gate, w_up, w_down = gated_setup(2, tokens=32, held=4)
    experts, k, held = 16, 4, (2, 5, 7, 11)
    probe = jnp.asarray(np.random.default_rng(3).normal(0, 1, x.shape),
                        jnp.float32)

    def loss(sparse, x, router, w_gate, w_up, w_down):
        chosen, weights = FE.route_softmax_raw(x, router, k)
        if sparse:
            out = FE.held_experts_raw(
                x, FE.local_ids(chosen, held, experts), weights, w_up,
                w_down, usual, w_gate)
        else:
            out = dense_gated(x, chosen, weights, held, w_gate, w_up, w_down)
        return jnp.sum(out * probe)
    args = (x, router, w_gate, w_up, w_down)
    got = jax.jit(jax.grad(loss, argnums=(1, 2, 3, 4, 5)),
                  static_argnums=0)(True, *args)
    want = jax.grad(loss, argnums=(1, 2, 3, 4, 5))(False, *args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4)


def test_gated_experts_through_the_kernels_in_the_interpreter():
    """128 rows are a whole tile: inside ``interpret_scope`` the three
    grouped products are the megablox kernels, and equal ``ragged_dot``'s
    (the path a CPU takes otherwise), value and gradients."""
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels import grouped_matmul as gm
    rng = np.random.default_rng(4)
    arr = lambda scale, *shape: jnp.asarray(rng.normal(0, scale, shape),
                                            jnp.float32)
    tokens, hidden, width, experts, k, held = 32, 128, 128, 8, 4, (1, 3, 4, 6)
    x, router = arr(1, tokens, hidden), arr(0.5, hidden, experts)
    ws = (arr(0.1, 4, hidden, width), arr(0.1, 4, hidden, width),
          arr(0.1, 4, width, hidden))
    chosen, weights = FE.route_softmax_raw(x, router, k)
    local = FE.local_ids(chosen, held, experts)
    assert tokens * k == 128 and gm.kernel_path(128, interpret=True)

    def summed(x, w_gate, w_up, w_down):
        return jnp.sum(jnp.sin(FE.held_experts_raw(
            x, local, weights, w_up, w_down, None, w_gate)))
    want = jax.value_and_grad(summed, argnums=(0, 1, 2, 3))(x, *ws)
    with fa.interpret_scope():
        got = jax.jit(jax.value_and_grad(summed, argnums=(0, 1, 2, 3)))(x, *ws)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def test_an_overflowing_step_goes_window_by_window():
    """Past the usual launch the same launch runs over window after window
    of the sorted assignments: as many as hold the assignments on held
    experts (they sort in front), an expert's run cut where a window ends."""
    x, router, w_gate, w_up, w_down = gated_setup(5, tokens=40, held=4)
    held, experts, k = (0, 1, 2, 3), 16, 4
    chosen, weights = FE.route_softmax_raw(x, router, k)
    local = FE.local_ids(chosen, held, experts)
    on_held = int((np.asarray(local) < 4).sum())
    assert on_held > 24
    assert int(FE._windows(local, 4, 8)) == -(-on_held // 8)
    assert int(FE._windows(local, 4, 10 ** 6)) == 1
    want = dense_gated(x, chosen, weights, held, w_gate, w_up, w_down)
    stacked = (w_gate, w_up, w_down)
    got = jax.jit(lambda: FE._every_window(x, local, weights, stacked, 8))()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # window 1 alone is the part of the sorted assignments 8..15
    one = jax.jit(lambda: FE._sorted_part(x, local, weights, stacked, 8,
                                          jnp.int32(8)))()
    first = jax.jit(lambda: FE._sorted_part(x, local, weights, stacked, 8))()
    both = jax.jit(lambda: FE._sorted_part(x, local, weights, stacked, 16))()
    np.testing.assert_allclose(first + one, both, rtol=1e-5, atol=1e-6)


def test_the_cells_launches_by_hand():
    # 16,384 tokens x 10 of 512, 32 held: 10,240 expected (320 an expert),
    # 30,720 launched (sixty tiles), 163,840 at worst
    assert FE.usual_rows(16384, 10, 32, 512) == 30720
    assert 16384 * min(10, 32) == 163840
    # that worst case goes window by window (1.25 GiB of float32 rows a
    # buffer), the 8k-token cell's in one launch (0.49 GiB)
    assert FE._windowed(163840, 2048) and not FE._windowed(49152, 2688)
    assert 100.0 * (30720 - 10240) / 30720 == pytest.approx(66.67, abs=0.01)


def test_the_layer_names_its_router_and_its_expert_form():
    from paddle_tpu.nn.layer.experts import (GatedMLP, RoutedExperts,
                                             SquaredReLUMLP)
    with pytest.raises(ValueError, match="neither"):
        RoutedExperts(8, 8, 4, 2, router="argmax")
    with pytest.raises(ValueError, match="neither"):
        RoutedExperts(8, 8, 4, 2, expert="gelu")
    old = RoutedExperts(8, 8, 4, 2, shared_intermediate_size=8)
    assert sorted(n for n, _ in old.named_parameters()) == [
        "experts.down_proj", "experts.up_proj", "gate.weight",
        "shared_experts.down_proj.weight", "shared_experts.up_proj.weight"]
    assert isinstance(old.shared_experts, SquaredReLUMLP)
    assert old.gate.e_score_correction_bias is not None
    new = RoutedExperts(8, 8, 4, 2, shared_intermediate_size=8,
                        router="softmax", expert="gated", shared_gate=True)
    assert isinstance(new.shared_experts, GatedMLP)
    assert not hasattr(new.gate, "e_score_correction_bias")
    assert {n for n, _ in new.named_parameters()} == {
        "gate.weight", "shared_gate", "experts.gate_proj", "experts.up_proj",
        "experts.down_proj", "shared_experts.gate_proj.weight",
        "shared_experts.up_proj.weight", "shared_experts.down_proj.weight"}
    assert not list(new.named_buffers())
    with pytest.raises(ValueError, match="held_experts"):
        Qwen3NextConfig.tiny(held_experts=(0, 1))
    with pytest.raises(ValueError, match="layer_types"):
        Qwen3NextConfig.tiny(recompute=("mamba",))
    assert Qwen3NextConfig.tiny(
        num_hidden_layers=4, full_attention_interval=4).layer_types == (
        "linear_attention",) * 3 + ("full_attention",)


# -- amp, and a step that trains ------------------------------------------------

KEPT = ("A_log", "dt_bias", "norm.weight", "norm_weight", "layernorm.weight",
        "gate.weight")


def tiny_model(**kw):
    paddle.seed(0)
    return Qwen3NextForCausalLM(Qwen3NextConfig.tiny(
        num_hidden_layers=4, full_attention_interval=4, **kw))


def test_decorate_keeps_the_marked_parameters_in_float32():
    model = tiny_model()
    paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    state = model.functional_state()
    for name, value in state.items():
        want = jnp.float32 if name.endswith(KEPT) else jnp.bfloat16
        assert value.dtype == want, name
    # 3 x (A_log, dt_bias, norm_weight) + 2 (q/k norms) + 8 layer norms
    # + the final norm + 4 routers
    assert sum(name.endswith(KEPT) for name in state) == 24
    assert sorted(n for n in state if "shared_gate" in n) == [
        "model.layers.%d.mlp.shared_gate" % i for i in range(4)]


def test_a_compiled_step_trains_in_bf16():
    from paddle_tpu import observability as obs
    model = tiny_model(recompute=("linear_attention",))
    paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    crit = Qwen3NextPretrainingCriterion()
    step = TrainStep(model, lambda lg, lb: crit(lg, lb),
                     paddle.optimizer.AdamW(parameters=model.parameters(),
                                            learning_rate=1e-3))
    before = obs.compile_counts().get("jit.train_step", 0)
    x = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 48)),
                    jnp.int32)
    losses = [float(step(x, x).numpy()) for _ in range(5)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.2
    assert obs.compile_counts()["jit.train_step"] - before == 1
    del step
    gc.collect()


# -- roles and the counter in a compiled step -----------------------------------

@pytest.fixture(scope="module")
def step_op_names():
    from paddle_tpu import observability as obs
    before = obs.default_registry().snapshot()
    model = tiny_model(recompute=("linear_attention",))
    crit = Qwen3NextPretrainingCriterion()
    step = TrainStep(model, lambda lg, lb: crit(lg, lb),
                     paddle.optimizer.AdamW(parameters=model.parameters(),
                                            learning_rate=1e-4))
    x = jnp.zeros((2, 32), jnp.int32)
    text = step._step.lower(*step.trace_args((x, x))).as_text(
        debug_info=True)
    after = obs.default_registry().snapshot()
    del step
    gc.collect()
    return set(re.findall(r'loc\("([^"]+)"', text)), before, after


@pytest.mark.parametrize("role", scopes.LINEAR + (
    scopes.MOE, scopes.MOE_EXPERTS, scopes.ATTN, scopes.NORM, scopes.EMBED,
    scopes.LM_HEAD))
def test_the_step_names_the_roles_forward_and_backward(step_op_names, role):
    names, _, _ = step_op_names
    mine = [n for n in names if scopes.scope_of(n) == role]
    assert any("transpose(" not in n for n in mine), (role, "no forward op")
    assert any("transpose(" in n for n in mine), (role, "no backward op")


def test_the_inner_role_wins_and_the_vocabulary_holds_both():
    assert scopes.scope_of(
        "jit(step_fn)/jvp(linear_attn)/linear_attn_scan/dot_general") == \
        "linear_attn_scan"
    assert scopes.scope_of("jit(step_fn)/jvp(linear_attn)/mul") == \
        "linear_attn"
    assert set(scopes.LINEAR) <= set(scopes.VOCABULARY)
    assert not set(scopes.LINEAR) & set(scopes.TRAIN + scopes.HYBRID)


def series(snapshot, name):
    return {tuple(s["labels"].values()): s["value"]
            for s in (snapshot.get(name) or {"series": []})["series"]}


def test_the_counters_count_at_trace_time(step_op_names):
    from paddle_tpu.observability.catalog import CATALOG
    assert CATALOG["linear_attn.scan_calls"]
    _, before, after = step_op_names
    delta = lambda name, key: (series(after, name).get(key, 0)
                               - series(before, name).get(key, 0))
    # three Gated DeltaNet layers, recomputed: traced for the forward and
    # again for the backward; four expert layers
    assert delta("linear_attn.scan_calls", ("chunked_jnp",)) >= 3
    # ... and what stands in front of each rule, at heads of 16 lanes
    assert CATALOG["ssm.conv_calls"]
    assert delta("ssm.conv_calls", ("jnp",)) >= 3
    assert delta("ssm.conv_calls", ("pallas",)) == 0
    calls = delta("moe.calls", ("ragged_dot",))
    assert calls >= 4
    tokens, k, held, width = 2 * 32, 2, 8, 8
    assert delta("moe.rows", ("routed",)) == calls * tokens * k
    assert delta("moe.rows", ("launched",)) == calls * FE.usual_rows(
        tokens, k, held, width)
