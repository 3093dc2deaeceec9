"""The program's side of the Nemotron-H family: the chunked scan against
the token-by-token recurrence, an expert layer that drops no token, the
key/value group in front of the attention kernels, ``amp.decorate``'s
keep-f32 mark, and the roles and counters of the new blocks in a compiled
training step."""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                          NemotronHForCausalLM,
                                          NemotronHPretrainingCriterion)
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional import experts as FE
from paddle_tpu.nn.functional import ssm as FS
from paddle_tpu.observability import scopes


def scan_inputs(seed, length, heads=4, p=8, groups=2, n=16, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.normal(0, 1.0, shape), dtype)
    x, b, c = arr(2, length, heads, p), arr(2, length, groups, n), \
        arr(2, length, groups, n)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, (2, length, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (heads,)), jnp.float32)
    d = jnp.asarray(rng.normal(1.0, 0.1, (heads,)), jnp.float32)
    return x, dt, a, b, c, d


@pytest.mark.parametrize("length,chunk", [
    (16, 16),     # one whole chunk
    (17, 16),     # one token across the boundary
    (40, 16),     # no multiple of the chunk
    (64, 16),     # four chunks: the state crosses three boundaries
    (7, 16),      # shorter than a chunk
    (40, 8)])
def test_chunked_scan_equals_the_recurrence(length, chunk):
    args = scan_inputs(length, length)
    want = FS.ssd_recurrence_raw(*args)
    got = FS.ssd_scan_raw(*args, chunk)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("length,chunk", [(40, 16), (64, 16)])
def test_chunked_scan_gradients_equal_the_recurrences(length, chunk):
    args = scan_inputs(3, length)
    probe = jnp.asarray(np.random.default_rng(9).normal(
        0, 1.0, args[0].shape), jnp.float32)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * probe)
    want = jax.grad(loss(FS.ssd_recurrence_raw), argnums=range(6))(*args)
    got = jax.grad(loss(lambda *a: FS.ssd_scan_raw(*a, chunk)),
                   argnums=range(6))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3)


def test_a_state_that_must_cross_chunks_is_carried():
    """One input at position 0 and a slow decay: every later chunk's output
    comes from the carried state alone."""
    x, dt, a, b, c, d = scan_inputs(1, 64)
    x = x.at[:, 1:].set(0.0)
    a = jnp.full_like(a, -0.01)
    y = FS.ssd_scan_raw(x, dt, a, b, c, jnp.zeros_like(d), 16)
    assert float(jnp.abs(y[:, 48:]).mean()) > 1e-3
    np.testing.assert_allclose(
        y, FS.ssd_recurrence_raw(x, dt, a, b, c, jnp.zeros_like(d)),
        rtol=2e-4, atol=2e-5)


def test_bf16_scan_keeps_decay_and_state_in_float32():
    args = scan_inputs(5, 64)
    want = FS.ssd_recurrence_raw(*args)
    x, dt, a, b, c, d = args
    got = FS.ssd_scan_raw(x.astype(jnp.bfloat16), dt, a,
                          b.astype(jnp.bfloat16), c.astype(jnp.bfloat16), d,
                          16)
    assert got.dtype == jnp.bfloat16
    err = jnp.linalg.norm(got.astype(jnp.float32) - want) / jnp.linalg.norm(
        want)
    assert float(err) < 0.02


def test_causal_conv_is_causal_and_depthwise():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (1, 12, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 1, (4, 6)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 1, (6,)), jnp.float32)
    y = FS.causal_conv1d_raw(x, w, bias)
    for t in (0, 2, 11):
        want = bias + sum(w[j] * x[0, t - 3 + j] for j in range(4)
                          if t - 3 + j >= 0)
        np.testing.assert_allclose(y[0, t], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(FS.causal_conv1d_raw(x, w, bias, silu=True),
                               jax.nn.silu(y), rtol=1e-6)


# -- the expert layer ----------------------------------------------------------

def dense_experts(x, chosen, weights, held, w_up, w_down):
    out = jnp.zeros_like(x)
    for j, e in enumerate(held):
        gate = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        out = out + gate[:, None] * (
            jnp.square(jax.nn.relu(x @ w_up[j])) @ w_down[j])
    return out


@pytest.mark.parametrize("usual", [None, 8, 32, 10 ** 6])
@pytest.mark.parametrize("held", [(0, 1, 2, 3, 4, 5, 6, 7), (3, 9, 12),
                                  (15,)])
def test_no_token_is_dropped_when_every_token_picks_the_same_experts(held,
                                                                     usual):
    """A selection bias that sends every token to experts 0..5: the held
    ones among them get every token, whether the usual launch holds them
    (32 rows do for one held expert of 24 tokens) or the step falls to the
    worst case (8 rows never do; 144 assignments on eight held experts)."""
    rng = np.random.default_rng(1)
    tokens, hidden, width, experts, k = 24, 16, 12, 16, 6
    x = jnp.asarray(rng.normal(0, 1, (tokens, hidden)), jnp.float32)
    router = jnp.asarray(rng.normal(0, 0.1, (hidden, experts)), jnp.float32)
    bias = jnp.where(jnp.arange(experts) < k, 10.0, 0.0)
    w_up = jnp.asarray(rng.normal(0, 0.3, (len(held), hidden, width)),
                       jnp.float32)
    w_down = jnp.asarray(rng.normal(0, 0.3, (len(held), width, hidden)),
                         jnp.float32)
    chosen, weights = FE.route_raw(x, router, bias, k, 2.5)
    assert set(np.asarray(chosen).ravel()) == set(range(k))
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    got = jax.jit(lambda *a: FE.held_experts_raw(*a, usual))(
        x, FE.local_ids(chosen, held, experts), weights, w_up, w_down)
    want = dense_experts(x, chosen, weights, held, w_up, w_down)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    if sum(e < k for e in held):    # every token got its part
        assert bool(jnp.all(jnp.abs(got).sum(-1) > 0))
    else:
        assert not bool(jnp.any(got))


def test_the_usual_launch_by_hand():
    # the cell: 8,192 tokens x 6 of 128, 8 held: 3,072 expected, 9,216
    # launched (three times that, eighteen tiles), 49,152 at worst
    assert FE.usual_rows(8192, 6, 8, 128) == 9216
    assert FE.usual_rows(8192, 6, 128, 128) == 8192 * 6      # nothing cut
    assert FE.usual_rows(64, 2, 8, 8) == 128                 # the worst case
    assert FE.usual_rows(1024, 6, 8, 128) == 1536            # three tiles


@pytest.mark.parametrize("usual", [None, 8, 64])
def test_expert_gradients_equal_the_dense_computation(usual):
    """Through the one launch, the usual one (64 rows hold the ~32 held
    assignments) and the fallback to the worst case (8 do not)."""
    rng = np.random.default_rng(2)
    tokens, hidden, width, experts, k, held = 32, 16, 12, 16, 4, (2, 5, 7, 11)
    x = jnp.asarray(rng.normal(0, 1, (tokens, hidden)), jnp.float32)
    router = jnp.asarray(rng.normal(0, 0.5, (hidden, experts)), jnp.float32)
    w_up = jnp.asarray(rng.normal(0, 0.3, (4, hidden, width)), jnp.float32)
    w_down = jnp.asarray(rng.normal(0, 0.3, (4, width, hidden)), jnp.float32)
    probe = jnp.asarray(rng.normal(0, 1, (tokens, hidden)), jnp.float32)

    def loss(sparse, x, router, w_up, w_down):
        chosen, weights = FE.route_raw(x, router, jnp.zeros(experts), k, 1.0)
        if sparse:
            out = FE.held_experts_raw(
                x, FE.local_ids(chosen, held, experts), weights, w_up,
                w_down, usual)
        else:
            out = dense_experts(x, chosen, weights, held, w_up, w_down)
        return jnp.sum(out * probe)
    got = jax.jit(jax.grad(loss, argnums=(1, 2, 3, 4)), static_argnums=0)(
        True, x, router, w_up, w_down)
    want = jax.grad(loss, argnums=(1, 2, 3, 4))(False, x, router, w_up,
                                                w_down)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("rows", [128, 384])
def test_grouped_matmul_kernels_equal_ragged_dot(rows):
    """The megablox path in the interpreter against the plain product:
    value, both gradients, zeros (not garbage) past the groups' total."""
    from paddle_tpu.kernels import grouped_matmul as gm
    rng = np.random.default_rng(rows)
    lhs = jnp.asarray(rng.normal(0, 1, (rows, 64)), jnp.float32)
    rhs = jnp.asarray(rng.normal(0, 1, (4, 64, 96)), jnp.float32)
    sizes = jnp.asarray([40, 0, 50, 20], jnp.int32)
    probe = jnp.asarray(rng.normal(0, 1, (rows, 96)), jnp.float32)
    assert gm.kernel_path(rows, interpret=True)
    assert not gm.kernel_path(rows, interpret=False)     # a CPU here
    assert not gm.kernel_path(100, interpret=True)       # no whole tile
    out = gm.grouped_matmul(lhs, rhs, sizes, interpret=True)
    assert not bool(jnp.any(out[110:]))
    loss = lambda interp: lambda a, b: jnp.sum(gm.grouped_matmul(
        a, b, sizes, interpret=interp) * probe)
    want = jax.value_and_grad(loss(False), argnums=(0, 1))(lhs, rhs)
    got = jax.jit(jax.value_and_grad(loss(True), argnums=(0, 1)))(lhs, rhs)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
    assert not bool(jnp.any(got[1][0][110:]))


def test_the_layer_refuses_experts_it_cannot_hold():
    from paddle_tpu.nn.layer.experts import RoutedExperts
    with pytest.raises(ValueError, match="distinct ids"):
        RoutedExperts(8, 8, 4, 2, held=(1, 1))
    with pytest.raises(ValueError, match="distinct ids"):
        RoutedExperts(8, 8, 4, 2, held=(4,))
    with pytest.raises(ValueError, match="held_experts"):
        NemotronHConfig.tiny(held_experts=(0, 1))
    with pytest.raises(ValueError, match="unknown block kinds"):
        NemotronHConfig.tiny(hybrid_override_pattern="MX")


# -- the key/value group -------------------------------------------------------

@pytest.mark.parametrize("kv_heads", [1, 2, 4])
def test_grouped_query_attention_reads_head_h_over_group(kv_heads):
    rng = np.random.default_rng(kv_heads)
    q = jnp.asarray(rng.normal(0, 1, (2, 24, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (2, 24, kv_heads, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (2, 24, kv_heads, 8)), jnp.float32)
    got = F.scaled_dot_product_attention(
        paddle.Tensor(q), paddle.Tensor(k), paddle.Tensor(v),
        is_causal=True)._array
    group = 4 // kv_heads
    for h in range(4):
        want = F.sdpa_reference_raw(q[:, :, h:h + 1],
                                    k[:, :, h // group:h // group + 1],
                                    v[:, :, h // group:h // group + 1],
                                    is_causal=True)
        np.testing.assert_allclose(got[:, :, h:h + 1], want, rtol=1e-5,
                                   atol=1e-5)
    two = jnp.concatenate([k[:, :, :1]] * 2, axis=2)
    with pytest.raises(ValueError, match="divisible"):
        F.scaled_dot_product_attention(
            paddle.Tensor(q[:, :, :3]), paddle.Tensor(two),
            paddle.Tensor(two), is_causal=True)


# -- amp -----------------------------------------------------------------------

KEPT = ("A_log", "mixer.D", "dt_bias", "norm.weight", "norm_weight",
        "norm_f.weight", "gate.weight")


def test_decorate_keeps_the_marked_parameters_in_float32():
    paddle.seed(0)
    model = NemotronHForCausalLM(NemotronHConfig.tiny(
        hybrid_override_pattern="ME*"))
    paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    state = model.functional_state()
    for name, value in state.items():
        want = jnp.float32 if name.endswith(KEPT) else jnp.bfloat16
        assert value.dtype == want, name
    assert sum(name.endswith(KEPT) for name in state) == 9
    bias = model.backbone.layers[1].mixer.gate.e_score_correction_bias
    assert bias.dtype == np.dtype("float32")
    # and the step holds them as they are: no bf16 compute copy of them
    step = TrainStep(model, lambda lg, lb: NemotronHPretrainingCriterion()(
        lg, lb), paddle.optimizer.AdamW(parameters=model.parameters(),
                                        learning_rate=1e-4))
    assert not [k for k in step._compute_dtypes if k.endswith(KEPT)]
    assert set(step._compute_dtypes) == {
        k for k in step.params if not k.endswith(KEPT)}


def test_decorate_leaves_gpt2s_types_as_they_were():
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny())
    paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    for name, value in model.functional_state().items():
        norm = ".ln" in name or "ln_f" in name
        assert value.dtype == (jnp.float32 if norm else jnp.bfloat16), name


def test_a_mark_on_any_parameter_is_honoured():
    layer = paddle.nn.Linear(4, 4)
    layer.bias.keep_fp32 = True
    paddle.amp.decorate(layer, level="O2", dtype="bfloat16")
    assert layer.weight.dtype == np.dtype("bfloat16")
    assert layer.bias.dtype == np.dtype("float32")


# -- roles and counters in a compiled step -------------------------------------

@pytest.fixture(scope="module")
def step_op_names():
    from paddle_tpu import observability as obs
    before = obs.default_registry().snapshot()
    paddle.seed(0)
    model = NemotronHForCausalLM(NemotronHConfig.tiny(
        hybrid_override_pattern="ME*", recompute="M"))
    crit = NemotronHPretrainingCriterion()
    step = TrainStep(model, lambda lg, lb: crit(lg, lb),
                     paddle.optimizer.AdamW(parameters=model.parameters(),
                                            learning_rate=1e-4))
    x = jnp.zeros((2, 32), jnp.int32)
    text = step._step.lower(*step.trace_args((x, x))).as_text(
        debug_info=True)
    after = obs.default_registry().snapshot()
    del step
    gc.collect()
    import re
    return set(re.findall(r'loc\("([^"]+)"', text)), before, after


@pytest.mark.parametrize("role", scopes.HYBRID + (scopes.ATTN, scopes.NORM,
                                                  scopes.EMBED,
                                                  scopes.LM_HEAD))
def test_the_step_names_the_new_roles_forward_and_backward(step_op_names,
                                                           role):
    names, _, _ = step_op_names
    mine = [n for n in names if scopes.scope_of(n) == role]
    assert any("transpose(" not in n for n in mine), (role, "no forward op")
    assert any("transpose(" in n for n in mine), (role, "no backward op")


def test_the_inner_role_wins():
    assert scopes.scope_of(
        "jit(step_fn)/jvp(ssm)/ssm_scan/dot_general") == "ssm_scan"
    assert scopes.scope_of(
        "jit(step_fn)/transpose(jvp(moe))/moe_experts/ragged_dot") == \
        "moe_experts"
    assert scopes.scope_of("jit(step_fn)/jvp(moe)/top_k") == "moe"
    assert set(scopes.HYBRID) <= set(scopes.VOCABULARY)
    assert not set(scopes.HYBRID) & set(scopes.TRAIN)


def series(snapshot, name):
    return {tuple(s["labels"].values()): s["value"]
            for s in (snapshot.get(name) or {"series": []})["series"]}


def test_the_counters_count_at_trace_time(step_op_names):
    _, before, after = step_op_names
    delta = lambda name, key: (series(after, name).get(key, 0)
                               - series(before, name).get(key, 0))
    # one Mamba-2 block, recomputed: traced for the forward and again for
    # the backward; one expert block, traced once
    assert delta("ssm.scan_calls", ("chunked_jnp",)) >= 1
    # ... and what stands in front of it, at 4 heads of 16: the jnp path
    assert delta("ssm.conv_calls", ("jnp",)) >= 1
    assert delta("ssm.conv_calls", ("pallas",)) == 0
    calls = delta("moe.calls", ("ragged_dot",))
    assert calls >= 1
    tokens, k, held, width = 2 * 32, 2, 8, 8
    assert delta("moe.rows", ("routed",)) == calls * tokens * k
    assert delta("moe.rows", ("expected_held",)) == \
        calls * tokens * k * held // width
    assert delta("moe.rows", ("launched",)) == calls * FE.usual_rows(
        tokens, k, held, width) == calls * 128
