"""The gated delta rule's Pallas kernels (``kernels/delta_rule.py``) in the
interpreter, on the CPU: forward and all five gradients against the
token-by-token recurrence and against the ``jnp`` chunked path they stand
in for, a state that must cross chunks (and a kernel that forgets it, which
must fail), a stiff system, the dispatch rule and the counter's label.
What Mosaic makes of them is ``tests/test_flash_tpu_compile.py``'s."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import delta_rule as K
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.nn.functional import linear_attn as FL

CHUNK, D = 64, 128
ARGS = ("q", "k", "v", "g", "beta")
# value heads a key head; two key heads, a batch of two
REPS = {"a-value-head": 1, "two-value-heads": 2}
# two grid steps of two spans of two chunks each (the carry and the reverse
# carry cross chunks, spans and steps), and a length that is padded up to
# three steps of one span
LENGTHS = {"8-chunks": 512, "padded": 300}


def rule_inputs(seed, length, rep, dtype=jnp.float32, key_heads=2, batch=2):
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.normal(0, 1.0, shape), jnp.float32)
    q = FL.l2_normalize_raw(arr(batch, length, key_heads, D), scale=D ** -0.5)
    k = FL.l2_normalize_raw(arr(batch, length, key_heads, D))
    v = arr(batch, length, key_heads * rep, D)
    g = -jnp.asarray(rng.uniform(0.001, 0.2, (batch, length,
                                              key_heads * rep)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, g.shape), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def kernel_rule(*args, chunk=CHUNK):
    with fa.interpret_scope():
        return FL.gated_delta_rule_raw(*args, chunk)


def jnp_rule(*args):
    return FL.gated_delta_rule_raw(*args, CHUNK)


def recurrence(*args):
    return FL.gated_delta_rule_recurrence_raw(
        *(t.astype(jnp.float32) for t in args))


@functools.lru_cache(maxsize=None)
def readings(rep, length, dtype):
    """(outputs, gradients) of the kernels, the ``jnp`` path and the
    recurrence on one set of inputs; the recurrence reads the inputs as the
    others do (rounded to ``dtype``) and computes in float32."""
    args = rule_inputs(7, LENGTHS[length], REPS[rep], jnp.dtype(dtype))
    probe = jnp.asarray(np.random.default_rng(9).normal(
        0, 1.0, args[2].shape), jnp.float32)
    out = {}
    for name, fn in (("kernel", kernel_rule), ("jnp", jnp_rule),
                     ("recurrence", recurrence)):
        loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * probe)
        out[name] = (fn(*args), jax.grad(loss, argnums=range(5))(*args))
    return out


def rel(got, want):
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# float32: the sums' order differs and nothing else; bf16: one rounding of
# each operand of each product (the tolerances of the jnp path's own tests,
# tests/test_qwen3_next.py: 0.02 forward, 0.03 a gradient)
LIMIT = {"float32": 2e-4, "bfloat16": 3e-2}
CASES = [("two-value-heads", "8-chunks", "float32"),
         ("two-value-heads", "8-chunks", "bfloat16"),
         ("two-value-heads", "padded", "float32"),
         ("a-value-head", "8-chunks", "float32"),
         ("a-value-head", "padded", "bfloat16")]


@pytest.mark.parametrize("against", ["recurrence", "jnp"])
@pytest.mark.parametrize("rep,length,dtype", CASES)
def test_forward(rep, length, dtype, against):
    r = readings(rep, length, dtype)
    got, want = r["kernel"][0], r[against][0]
    assert got.dtype == jnp.dtype(dtype) and got.shape == want.shape
    assert rel(got, want) < min(LIMIT[dtype], 2e-2)


@pytest.mark.parametrize("against", ["recurrence", "jnp"])
@pytest.mark.parametrize("arg", ARGS)
@pytest.mark.parametrize("rep,length,dtype", CASES)
def test_gradient(rep, length, dtype, arg, against):
    r = readings(rep, length, dtype)
    i = ARGS.index(arg)
    got, want = r["kernel"][1][i], r[against][1][i]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert rel(got, want) < LIMIT[dtype]
    # ... and no further from the float32 recurrence than the jnp path is,
    # half as far again at most
    assert rel(got, r["recurrence"][1][i]) < max(
        1.5 * rel(r["jnp"][1][i], r["recurrence"][1][i]), 1e-5)


@pytest.mark.parametrize("chunk", [32, 128])
def test_the_result_does_not_depend_on_the_chunk(chunk):
    """Four chunks a span, and one: the same rule."""
    args = rule_inputs(11, 256, 2)
    assert rel(kernel_rule(*args, chunk=chunk), recurrence(*args)) < 2e-4
    loss = lambda fn: lambda *a: jnp.sum(jnp.square(fn(*a)))
    got = jax.grad(loss(functools.partial(kernel_rule, chunk=chunk)),
                   argnums=range(5))(*args)
    want = jax.grad(loss(recurrence), argnums=range(5))(*args)
    for g, w in zip(got, want):
        assert rel(g, w) < 2e-4


def crossing_inputs():
    """One write at position 0 and a slow decay: every later chunk's output
    comes from the carried state alone; a loss on the last span reaches
    position 0 through the reverse carry alone."""
    q, k, v, g, beta = rule_inputs(1, 512, 2)
    first = jnp.arange(512)[None, :, None] == 0
    return (q, k, v, jnp.full_like(g, -0.001), jnp.where(first, beta, 0.0))


def last_span_loss(fn):
    return lambda *a: jnp.sum(jnp.square(fn(*a)[:, 384:]))


def test_a_state_that_must_cross_chunks_is_carried_both_ways():
    args = crossing_inputs()
    o = kernel_rule(*args)
    assert float(jnp.abs(o[:, 384:]).mean()) > 1e-5
    assert rel(o, recurrence(*args)) < 2e-4
    got = jax.grad(last_span_loss(kernel_rule), argnums=(1, 2))(*args)
    want = jax.grad(last_span_loss(recurrence), argnums=(1, 2))(*args)
    assert float(jnp.abs(got[1][:, 0]).mean()) > 1e-6
    for g, w in zip(got, want):
        assert rel(g, w) < 2e-4


@pytest.fixture
def forgetful(monkeypatch):
    """The kernels with a carried state that is zeroed at EVERY grid step
    of 256 tokens.  The kernels' builders are jitted: traced afresh around
    the patch."""
    def zero_always(ref):
        ref[...] = jnp.zeros_like(ref)
    for builder in (K._forward, K._backward):
        builder.clear_cache()
    monkeypatch.setattr(K, "_zero_at_first", zero_always)
    yield
    for builder in (K._forward, K._backward):
        builder.clear_cache()


def test_a_forward_that_forgets_its_state_fails(forgetful):
    args = crossing_inputs()
    o = kernel_rule(*args)
    assert rel(o, recurrence(*args)) > 0.5
    assert float(jnp.abs(o[:, 256:]).max()) == 0.0


def test_a_backward_that_forgets_its_state_fails(forgetful):
    args = crossing_inputs()
    got = jax.grad(last_span_loss(kernel_rule), argnums=2)(*args)
    assert float(jnp.abs(got[:, 0]).max()) == 0.0


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_stiff_system_is_solved(dtype):
    """Keys that repeat and write strengths near one: the chunk's system
    has entries near one below its diagonal (``tests/test_qwen3_next.py``'s
    stiff matrix as the kernel meets it), whose powers grow like binomials
    before they vanish; block forward substitution does not care."""
    q, k, v, g, beta = rule_inputs(5, 256, 2, jnp.dtype(dtype))
    rng = np.random.default_rng(5)
    k = FL.l2_normalize_raw(jnp.broadcast_to(k[:, :1], k.shape).astype(
        jnp.float32) + 0.05 * jnp.asarray(rng.normal(0, 1, k.shape),
                                          jnp.float32)).astype(k.dtype)
    beta = jnp.asarray(rng.uniform(0.8, 1.0, beta.shape), jnp.float32)
    g = jnp.full_like(g, -1e-3)
    args = (q, k, v, g, beta)
    kk = jnp.einsum("bld,bsd->bls", k[:, :64, 0].astype(jnp.float32),
                    k[:, :64, 0].astype(jnp.float32))
    assert float(jnp.tril(kk, -1).sum() / (64 * 63 / 2) / kk.shape[0]) > 0.6
    want = recurrence(*args)
    assert rel(kernel_rule(*args), want) < max(
        2 * rel(jnp_rule(*args), want), 1e-4)
    loss = lambda fn: lambda *a: jnp.sum(jnp.square(
        fn(*a).astype(jnp.float32)))
    grads = {name: jax.grad(loss(fn), argnums=range(5))(*args)
             for name, fn in (("kernel", kernel_rule), ("jnp", jnp_rule),
                              ("recurrence", recurrence))}
    for got, other, exact in zip(grads["kernel"], grads["jnp"],
                                 grads["recurrence"]):
        assert rel(got, exact) < max(2 * rel(other, exact), 1e-3)


# (chunk, value heads a key head, key lanes, value lanes) -> taken
RULE = [((64, 2, 128, 128), True),      # the cell's
        ((64, 1, 128, 128), True),
        ((128, 4, 128, 256), True),
        ((32, 2, 256, 128), True),
        ((16, 2, 128, 128), True),
        ((64, 2, 64, 128), False),      # key heads of half a lane tile
        ((64, 2, 128, 192), False),     # value heads that straddle tiles
        ((256, 2, 128, 128), False),    # a chunk past a span
        ((48, 2, 128, 128), False),     # no power of two
        ((64, 8, 128, 128), False),     # more value heads than turn over
        ((16, 2, 16, 16), False)]       # the tiny test configuration


@pytest.mark.parametrize("shape,takes", RULE)
def test_the_kernels_take_whole_lane_tiles_on_a_tpu(monkeypatch, shape,
                                                    takes):
    assert jax.default_backend() == "cpu"
    assert not K.supported(*shape)                  # a CPU: never
    assert K.supported(*shape, interpret=True) is takes
    with fa.interpret_scope():
        assert K.supported(*shape) is takes
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert K.supported(*shape) is takes


def scan_calls():
    from paddle_tpu.observability import registry
    snap = registry.default_registry().snapshot().get(
        "linear_attn.scan_calls")
    return {s["labels"]["path"]: s["value"]
            for s in (snap or {"series": []})["series"]}


@pytest.mark.parametrize("interpreted,d,chunk,path", [
    (True, 128, 64, "pallas"),
    (False, 128, 64, "chunked_jnp"),        # a CPU
    (True, 16, 16, "chunked_jnp"),          # below the shape rule
])
def test_the_counter_names_the_path(interpreted, d, chunk, path):
    rng = np.random.default_rng(0)
    arr = lambda *shape: jnp.asarray(rng.normal(0, 1, shape), jnp.float32)
    q = FL.l2_normalize_raw(arr(1, 2 * chunk, 1, d), scale=d ** -0.5)
    k = FL.l2_normalize_raw(arr(1, 2 * chunk, 1, d))
    v = arr(1, 2 * chunk, 2, d)
    g = jnp.full((1, 2 * chunk, 2), -0.05, jnp.float32)
    beta = jnp.full((1, 2 * chunk, 2), 0.5, jnp.float32)
    before = scan_calls()
    if interpreted:
        with fa.interpret_scope():
            o = FL.gated_delta_rule_raw(q, k, v, g, beta, chunk)
    else:
        o = FL.gated_delta_rule_raw(q, k, v, g, beta, chunk)
    after = scan_calls()
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("pallas", "chunked_jnp")}
    assert delta == {"pallas": int(path == "pallas"),
                     "chunked_jnp": int(path == "chunked_jnp")}
    assert rel(o, FL.gated_delta_rule_recurrence_raw(q, k, v, g, beta)) \
        < 2e-4


def test_a_recomputed_block_keeps_no_states_from_its_first_forward():
    """Under ``jax.checkpoint`` the first forward is the primal (o alone);
    the recomputation writes the states that entered its grid steps and
    the inverses for the backward."""
    args = rule_inputs(3, 256, 2)
    loss = lambda *a: jnp.sum(K.delta_rule(*a, CHUNK, True))
    text = str(jax.make_jaxpr(jax.value_and_grad(jax.checkpoint(loss)))(
        *args))
    calls = [line.split(" = pallas_call[")[0] for line in text.splitlines()
             if " = pallas_call[" in line]
    states = "f32[2,2,1,128,256]"       # (B, Hk, grid steps, D, R*P)
    assert len(calls) == 3              # forward, forward again, backward
    assert [states in outputs for outputs in calls] == [False, True, False]
