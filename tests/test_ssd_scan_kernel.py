"""The Mamba-2 scan's Pallas kernels (``kernels/ssd_scan.py``) in the
interpreter, on the CPU: forward and all six gradients against the
token-by-token recurrence and against the ``jnp`` scan they stand in for,
a state that must cross chunks (and a kernel that forgets it, which must
fail), the dispatch rule and the counter's label.  What Mosaic makes of
them is ``tests/test_flash_tpu_compile.py``'s."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import ssd_scan as K
from paddle_tpu.nn.functional import ssm as FS

CHUNK = N = 128
ARGS = ("x", "dt", "a", "b", "c", "d")
# (heads, head size): two heads of 64 share a lane tile; one head of 128
# fills it.  Two groups, a batch of two.
SHAPES = {"two-heads-a-tile": (4, 64), "a-head-a-tile": (2, 128)}
# three whole chunks (the carry and the reverse carry cross two
# boundaries), and a length that is padded up to them
LENGTHS = {"3-chunks": 384, "padded": 300}


def scan_inputs(seed, length, heads, p, dtype=jnp.float32, groups=2):
    rng = np.random.default_rng(seed)
    arr = lambda *shape: jnp.asarray(rng.normal(0, 1.0, shape), dtype)
    x, b, c = arr(2, length, heads, p), arr(2, length, groups, N), \
        arr(2, length, groups, N)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (2, length, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (heads,)), jnp.float32)
    d = jnp.asarray(rng.normal(1.0, 0.1, (heads,)), jnp.float32)
    return x, dt, a, b, c, d


def kernel_scan(*args):
    with fa.interpret_scope():
        return FS.ssd_scan_raw(*args, CHUNK)


def jnp_scan(*args):
    return FS.ssd_scan_raw(*args, CHUNK)


def recurrence(*args):
    return FS.ssd_recurrence_raw(*(t.astype(jnp.float32) for t in args))


@functools.lru_cache(maxsize=None)
def readings(shape, length, dtype):
    """(outputs, gradients) of the kernels, the ``jnp`` scan and the
    recurrence on one set of inputs; the recurrence reads the inputs as the
    others do (rounded to ``dtype``) and computes in float32."""
    heads, p = SHAPES[shape]
    args = scan_inputs(7, LENGTHS[length], heads, p, jnp.dtype(dtype))
    probe = jnp.asarray(np.random.default_rng(9).normal(
        0, 1.0, args[0].shape), jnp.float32)
    out = {}
    for name, fn in (("kernel", kernel_scan), ("jnp", jnp_scan),
                     ("recurrence", recurrence)):
        loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * probe)
        out[name] = (fn(*args), jax.grad(loss, argnums=range(6))(*args))
    return out


def rel(got, want):
    got, want = (jnp.asarray(t, jnp.float32) for t in (got, want))
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# float32: the sums' order differs and nothing else; bf16: one rounding of
# each operand of each product (the jnp scan stands 2.3e-3 from the float32
# recurrence forward on the same inputs)
LIMIT = {"float32": 2e-4, "bfloat16": 2e-2}
CASES = [("two-heads-a-tile", "3-chunks", "float32"),
         ("two-heads-a-tile", "3-chunks", "bfloat16"),
         ("two-heads-a-tile", "padded", "float32"),
         ("a-head-a-tile", "3-chunks", "float32"),
         ("a-head-a-tile", "padded", "bfloat16")]


@pytest.mark.parametrize("against", ["recurrence", "jnp"])
@pytest.mark.parametrize("shape,length,dtype", CASES)
def test_forward(shape, length, dtype, against):
    r = readings(shape, length, dtype)
    got, want = r["kernel"][0], r[against][0]
    assert got.dtype == jnp.dtype(dtype) and got.shape == want.shape
    assert rel(got, want) < LIMIT[dtype]


@pytest.mark.parametrize("against", ["recurrence", "jnp"])
@pytest.mark.parametrize("arg", ARGS)
@pytest.mark.parametrize("shape,length,dtype", CASES)
def test_gradient(shape, length, dtype, arg, against):
    r = readings(shape, length, dtype)
    i = ARGS.index(arg)
    got, want = r["kernel"][1][i], r[against][1][i]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert rel(got, want) < LIMIT[dtype]
    # ... and about as far from the float32 recurrence as the jnp scan is
    # (a sum of two heads' roundings swings: three times)
    assert rel(got, r["recurrence"][1][i]) < max(
        3 * rel(r["jnp"][1][i], r["recurrence"][1][i]), 1e-5)


def crossing_inputs():
    """One input at position 0 and a slow decay: every later chunk's
    output comes from the carried state alone; a loss on the last chunk
    reaches position 0 through the reverse carry alone."""
    x, dt, a, b, c, d = scan_inputs(1, 384, 4, 64)
    return (x.at[:, 1:].set(0.0), dt, jnp.full_like(a, -0.01), b, c,
            jnp.zeros_like(d))


def last_chunk_loss(fn):
    return lambda *a: jnp.sum(jnp.square(fn(*a)[:, 256:]))


def test_a_state_that_must_cross_chunks_is_carried_both_ways():
    args = crossing_inputs()
    y = kernel_scan(*args)
    assert float(jnp.abs(y[:, 256:]).mean()) > 1e-3
    assert rel(y, recurrence(*args)) < 2e-4
    got = jax.grad(last_chunk_loss(kernel_scan), argnums=(0, 3))(*args)
    want = jax.grad(last_chunk_loss(recurrence), argnums=(0, 3))(*args)
    assert float(jnp.abs(got[0][:, 0]).mean()) > 1e-3
    for g, w in zip(got, want):
        assert rel(g, w) < 2e-4


@pytest.fixture
def forgetful(monkeypatch):
    """The kernels with a carried state that is zeroed at EVERY chunk.  The
    kernels' builders are jitted: traced afresh around the patch."""
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
    y = kernel_scan(*args)
    assert rel(y, recurrence(*args)) > 0.5
    assert float(jnp.abs(y[:, 128:]).max()) == 0.0


def test_a_backward_that_forgets_its_state_fails(forgetful):
    args = crossing_inputs()
    got = jax.grad(last_chunk_loss(kernel_scan))(*args)
    assert float(jnp.abs(got[:, 0]).max()) == 0.0


# (chunk, heads a group, head size, state size) -> the kernels take it
RULE = [((128, 8, 64, 128), True),      # the hybrid cell's
        ((256, 2, 64, 128), True),
        ((128, 1, 128, 256), True),
        ((128, 1, 256, 128), True),
        ((128, 16, 8, 128), True),
        ((64, 8, 64, 128), False),      # a chunk of half a lane tile
        ((128, 8, 64, 64), False),      # a state of half a lane tile
        ((128, 1, 64, 128), False),     # a group of half a lane tile
        ((128, 8, 48, 128), False),     # heads that straddle lane tiles
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
    snap = registry.default_registry().snapshot().get("ssm.scan_calls")
    return {s["labels"]["path"]: s["value"]
            for s in (snap or {"series": []})["series"]}


@pytest.mark.parametrize("interpreted,heads,p,n,chunk,path", [
    (True, 4, 64, 128, 128, "pallas"),
    (False, 4, 64, 128, 128, "chunked_jnp"),      # a CPU
    (True, 4, 16, 16, 16, "chunked_jnp"),         # below the shape rule
])
def test_the_counter_names_the_path(interpreted, heads, p, n, chunk, path):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (1, 2 * chunk, heads, p)), jnp.float32)
    b = c = jnp.asarray(rng.normal(0, 1, (1, 2 * chunk, 2, n)), jnp.float32)
    dt = jnp.full((1, 2 * chunk, heads), 0.05, jnp.float32)
    a, d = -jnp.ones((heads,), jnp.float32), jnp.ones((heads,), jnp.float32)
    before = scan_calls()
    if interpreted:
        with fa.interpret_scope():
            y = FS.ssd_scan_raw(x, dt, a, b, c, d, chunk)
    else:
        y = FS.ssd_scan_raw(x, dt, a, b, c, d, chunk)
    after = scan_calls()
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("pallas", "chunked_jnp")}
    assert delta == {"pallas": int(path == "pallas"),
                     "chunked_jnp": int(path == "chunked_jnp")}
    assert rel(y, FS.ssd_recurrence_raw(x, dt, a, b, c, d)) < 2e-4


def test_a_recomputed_block_keeps_no_states_from_its_first_forward():
    """Under ``jax.checkpoint`` the first forward is the primal (y alone);
    the recomputation writes the entering states for the backward."""
    args = scan_inputs(3, 256, 4, 64)
    loss = lambda *a: jnp.sum(K.ssd_scan(*a, CHUNK, True))
    text = str(jax.make_jaxpr(jax.value_and_grad(jax.checkpoint(loss)))(
        *args))
    calls = [line.split(" = pallas_call[")[0] for line in text.splitlines()
             if " = pallas_call[" in line]
    states = "f32[2,2,2,128,128]"       # (B, G, chunks, N, R*P)
    assert len(calls) == 3              # forward, forward again, backward
    assert [states in outputs for outputs in calls] == [False, True, False]
