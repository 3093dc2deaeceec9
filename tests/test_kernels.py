"""Pallas flash-attention kernel tests, run on CPU via interpret=True.

The Pallas path is gated off CPU at dispatch level (kernels/flash_attention.py
supported()), so without interpret-mode tests the hottest custom code in the
repo would only ever execute on TPU.  Parity target: the O(S^2) XLA reference
(_reference_bhsd), same contract OpTest uses numpy for (reference
unittests/op_test.py:289).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.flash_attention_pallas import (_reference_bhsd,
                                                       flash_attention_bhsd)

SHAPES = [(2, 2, 256, 64), (1, 3, 128, 128), (2, 1, 384, 64)]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", SHAPES)
def test_flash_forward_matches_reference(causal, shape):
    b, h, s, d = shape
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    out = flash_attention_bhsd(q, k, v, causal=causal, interpret=True)
    ref = _reference_bhsd(q, k, v, causal, 1.0 / d ** 0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", SHAPES)
def test_flash_backward_matches_reference(causal, shape):
    b, h, s, d = shape
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)

    # sin() makes the cotangent non-uniform so dq/dk/dv all get real signal
    def f(q_, k_, v_):
        return jnp.sum(jnp.sin(flash_attention_bhsd(
            q_, k_, v_, causal=causal, interpret=True)))

    def r(q_, k_, v_):
        return jnp.sum(jnp.sin(_reference_bhsd(q_, k_, v_, causal,
                                               1.0 / d ** 0.5)))

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("dq dk dv".split(), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("block_q,block_k", [(128, 128), (128, 64),
                                             (128, 256)])
def test_flash_block_size_grid_edges(block_q, block_k, causal):
    # (128, 256) only stays wide-K on the non-causal path (causal clamps
    # block_k to block_q); both variants must match the reference
    b, h, s, d = 1, 2, 256, 64
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    out = flash_attention_bhsd(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=True)
    ref = _reference_bhsd(q, k, v, causal, 1.0 / d ** 0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_streamed_long_seq_path():
    """Sequences whose K/V exceed the resident budget take the
    grid-streamed forward — same numerics (checked in interpret mode with
    a tiny budget override)."""
    import paddle_tpu.kernels.flash_attention_pallas as fp
    b, h, s, d = 1, 2, 512, 64
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    old = fp._RESIDENT_KV_BUDGET
    fp._RESIDENT_KV_BUDGET = 1  # force the streamed path
    try:
        out = flash_attention_bhsd(q, k, v, causal=True, block_q=128,
                                   block_k=128, interpret=True)
    finally:
        fp._RESIDENT_KV_BUDGET = old
    ref = _reference_bhsd(q, k, v, True, 1.0 / d ** 0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_bf16_grad_finite():
    b, h, s, d = 1, 2, 128, 64
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)

    def f(q_):
        return jnp.sum(flash_attention_bhsd(
            q_, k, v, causal=True, interpret=True).astype(jnp.float32))

    g = jax.grad(f)(q)
    assert g.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


def test_flash_split_head_groups_grad_parity():
    """h=8, d=64, s=256 picks hg_f=8 (resident fits) vs hg_b=4 — the
    lse-regroup path in _flash_vjp_bwd must produce reference grads."""
    import paddle_tpu.kernels.flash_attention_pallas as fp
    b, h, s, d = 1, 8, 256, 64
    hg_b = fp._pick_head_group(h, d, s)
    hg_f = fp._pick_fwd_head_group(h, d, s, hg_b)
    assert hg_f != hg_b, (hg_f, hg_b)   # the regroup path IS exercised
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)

    def loss_pallas(q, k, v):
        out = flash_attention_bhsd(q, k, v, causal=True, interpret=True)
        return jnp.sum(out * out)

    def loss_ref(q, k, v):
        out = _reference_bhsd(q, k, v, True, 1.0 / d ** 0.5)
        return jnp.sum(out * out)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, w, name in zip(gp, gr, "q k v".split()):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=2e-3,
                                   rtol=2e-3, err_msg=f"d{name}")


def test_flash_bwd_split_long_seq_parity():
    """The split two-kernel backward (taken when the merged kernel's
    full-sequence dq scratch would blow VMEM) matches the merged backward's
    grads — tested at a sequence length ABOVE the merged budget for the
    chosen head group (interpret mode)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.kernels import flash_attention_pallas as fap

    b, s, h, d = 1, 1024, 2, 64     # hg=2 -> hgd=128
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32) * 0.2
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32) * 0.2
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32) * 0.2
    ct = jnp.asarray(rng.randn(b, s, h, d), jnp.float32) * 0.1

    def loss(q, k, v, budget):
        # the resident rung off: this is merged against split
        old = fap._DQ_SCRATCH_BUDGET, fap._RESIDENT_BWD_BUDGET
        fap._DQ_SCRATCH_BUDGET, fap._RESIDENT_BWD_BUDGET = budget, 0
        try:
            out = fap.flash_attention_bshd_native(
                q, k, v, causal=True, block_q=256, block_k=256,
                interpret=True)
        finally:
            fap._DQ_SCRATCH_BUDGET, fap._RESIDENT_BWD_BUDGET = old
        return jnp.sum(out * ct)

    # merged path (budget comfortably fits s*hgd*4 = 512KB)
    g_merged = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, 4 * 1024 * 1024)
    # split path (budget below the dq scratch need)
    g_split = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, 64 * 1024)
    for gm, gs, name in zip(g_merged, g_split, "qkv"):
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gm),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_flash_with_lse_matches_reference_and_grads():
    """flash_attention_bshd_with_lse (r4 verdict #3): the (out, lse) pair
    matches dense attention + logsumexp, and grads stay exact when the
    LOSS CONSUMES BOTH outputs (the dlse term folds into the backward
    kernels as delta - dlse)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention_pallas import \
        flash_attention_bshd_with_lse
    from paddle_tpu.nn.functional.attention import sdpa_reference_raw

    b, s, h, d = 1, 256, 2, 64
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    for causal in (False, True):
        out, lse = flash_attention_bshd_with_lse(q, k, v, causal=causal,
                                                 interpret=True)
        ref = sdpa_reference_raw(q, k, v, is_causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        # reference lse
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            mask = jnp.tril(jnp.ones((s, s), bool))
            logits = jnp.where(mask, logits, -1e30)
        ref_lse = jnp.moveaxis(jax.scipy.special.logsumexp(logits, -1),
                               1, -1)                    # (b, s, h)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=1e-4, atol=1e-4)

    # grads with an lse-consuming loss (the ring combine shape)
    def loss_flash(q_, k_, v_):
        out, lse = flash_attention_bshd_with_lse(q_, k_, v_, causal=True,
                                                 interpret=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_ref(q_, k_, v_):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q_, k_) * scale
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, -1e30)
        p = jax.nn.softmax(logits, -1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v_)
        lse = jnp.moveaxis(jax.scipy.special.logsumexp(logits, -1), 1, -1)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# GPTAttention hands the fused projection to the packed kernels exactly where
# the rule says (PR 32): no cache, attention dropout inactive, the shape
# supported, a part whole lane-aligned blocks, no live head axis.  The
# counter flash.fwd_calls{operands} says which way each traced call went.
# ---------------------------------------------------------------------------

def _fwd_calls():
    from paddle_tpu.observability import registry as reg
    ctr = reg.counter("flash.fwd_calls", ("operands",))
    return {o: ctr.labels(operands=o).value for o in ("packed", "split")}


# (config overrides, sequence, training, mesh axes, a cache?, flash forward
# calls traced as (packed, split): (0, 0) is the XLA reference path)
ATTENTION = [
    pytest.param({}, 128, True, None, False, (1, 0), id="training"),
    pytest.param({"attention_dropout_prob": 0.1}, 128, False, None, False,
                 (1, 0), id="eval-of-a-model-with-dropout"),
    pytest.param({"attention_dropout_prob": 0.1}, 128, True, None, False,
                 (0, 0), id="dropout-live"),
    pytest.param({}, 128, True, None, True, (0, 1), id="a-cache-is-passed"),
    pytest.param({}, 128, True, {"mp": 2}, False, (0, 1), id="mp-mesh"),
    pytest.param({}, 128, True, {"dp": 2}, False, (1, 0), id="dp-mesh"),
    pytest.param({}, 96, True, None, False, (0, 0),
                 id="unsupported-sequence-length"),
    pytest.param({"hidden_size": 64, "num_attention_heads": 1}, 128, True,
                 None, False, (0, 1), id="a-part-of-half-a-lane-block"),
]


@pytest.mark.parametrize("overrides,s,training,axes,cached,calls", ATTENTION)
def test_gpt_attention_takes_the_packed_path_where_the_rule_says(
        monkeypatch, overrides, s, training, axes, cached, calls):
    import contextlib
    import dataclasses

    import paddle_tpu as paddle
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.jit import functional_call
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.models.gpt import GPTAttention, GPTConfig
    from paddle_tpu.nn import functional as F

    if axes and len(jax.devices()) < 2:
        pytest.skip("needs two devices (conftest gives the CPU eight)")
    config = dataclasses.replace(
        GPTConfig.tiny(), **{"hidden_size": 128, "num_attention_heads": 2,
                             "max_position_embeddings": 256, **overrides})
    paddle.seed(7)
    attn = GPTAttention(config)
    attn.train() if training else attn.eval()
    b, hidden = 2, config.hidden_size
    x = jnp.asarray(np.random.RandomState(1).randn(b, s, hidden),
                    jnp.float32)
    empty = jnp.zeros((b, 0, config.num_attention_heads,
                       hidden // config.num_attention_heads), jnp.float32)

    def loss(state, x_):
        args = (paddle.Tensor(x_),) + (
            ((paddle.Tensor(empty), paddle.Tensor(empty)),) if cached
            else ())
        out, _ = functional_call(attn, state, *args, rng=jax.random.key(0))
        return jnp.sum(jnp.sin((out[0] if cached else out)))

    def run():
        mesh = contextlib.nullcontext() if axes is None else \
            mesh_mod.mesh_scope(jax.sharding.Mesh(
                np.asarray(jax.devices()[:2]), tuple(axes)))
        with fa.interpret_scope(), mesh:
            before = _fwd_calls()
            # a fresh wrapper: dispatch reads the scopes at trace time
            got = jax.jit(jax.value_and_grad(lambda *a: loss(*a),
                                             argnums=(0, 1)))(
                attn.functional_state(), x)
            after = _fwd_calls()
        return got, tuple(after[o] - before[o] for o in ("packed", "split"))

    got, counted = run()
    assert counted == calls
    if calls[0]:
        # and where it is taken it is the sliced layer's result, bit for bit
        monkeypatch.setattr(F, "packed_attention_supported",
                            lambda *a, **k: False)
        want, recounted = run()
        assert recounted == (0, 1)
        for a, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert bool(jnp.all(a == w))
