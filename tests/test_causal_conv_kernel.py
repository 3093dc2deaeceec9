"""What stands in front of the recurrent scans as Pallas kernels
(``kernels/causal_conv.py``) in the interpreter, on the CPU: the parts and
the gradients of the projection's buffer, the taps and the bias against the
``jnp`` path they stand in for (``causal_conv1d_raw`` on a slice, slices,
``l2_normalize_raw``), over several token blocks so that the rows in front
of a block and, in the backward, the rows behind it cross block
boundaries; the dispatch rule and the counter's label.  What Mosaic makes
of them is ``tests/test_flash_tpu_compile.py``'s."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import causal_conv as K
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.nn.functional import ssm as FS

TAPS, BATCH, SEQ = 4, 2, 96
#: tokens a grid step takes here: three blocks a row
BLOCK = 32
# (buffer width, offset, parts, bias?).  A Mamba-2 mixer's form: [z | x B C
# | dt], the convolved columns 4 lane tiles in, a ragged last tile in the
# buffer, a bias, no normalisation.  A Gated DeltaNet's: [q k v | z], no
# bias, q and k normalised a head of one lane tile with different scales.
FORMS = {
    "mamba2": (512 + 512 + 64, 512,
               ((256, None, 1.0), (128, None, 1.0), (128, None, 1.0)), True),
    "delta-net": (640 + 256, 0,
                  ((256, 128, 128 ** -0.5), (256, 128, 1.0),
                   (128, None, 1.0)), False),
}


@pytest.fixture(autouse=True)
def three_blocks_a_row(monkeypatch):
    monkeypatch.setattr(K, "_BLOCK_TOKENS", BLOCK)


def inputs(form, dtype, seed=5):
    width, _, parts, bias = FORMS[form]
    channels = sum(p[0] for p in parts)
    rng = np.random.default_rng(seed)
    proj = jnp.asarray(rng.normal(0, 1.0, (BATCH, SEQ, width)), dtype)
    taps = jnp.asarray(rng.uniform(-0.5, 0.5, (TAPS, channels)), dtype)
    b = jnp.asarray(rng.normal(0, 0.2, (channels,)), dtype) if bias else None
    return proj, taps, b


def split(form, proj, taps, bias, interpreted):
    _, offset, parts, _ = FORMS[form]
    if not interpreted:
        return FS.conv_split_raw(proj, offset, parts, taps, bias, silu=True)
    with fa.interpret_scope():
        return FS.conv_split_raw(proj, offset, parts, taps, bias, silu=True)


@functools.lru_cache(maxsize=None)
def readings(form, dtype):
    """{path: (parts, gradients of (buffer, taps[, bias]))} of the kernels
    and of the ``jnp`` path on one set of inputs."""
    proj, taps, bias = inputs(form, jnp.dtype(dtype))
    rng = np.random.default_rng(11)
    probes = [jnp.asarray(rng.normal(0, 1.0, (BATCH, SEQ, p[0])),
                          jnp.float32) for p in FORMS[form][2]]
    wrt = (0, 1, 2) if bias is not None else (0, 1)
    out = {}
    for path in ("pallas", "jnp"):
        fn = functools.partial(split, form, interpreted=path == "pallas")
        loss = lambda *a: sum(jnp.sum(o.astype(jnp.float32) * p)
                              for o, p in zip(fn(*a), probes))
        out[path] = (fn(proj, taps, bias),
                     jax.grad(loss, argnums=wrt)(proj, taps, bias))
    return out


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


CASES = [(form, dtype) for form in FORMS for dtype in ("float32", "bfloat16")]


@pytest.mark.parametrize("form,dtype", CASES)
def test_the_parts_are_the_jnp_paths(form, dtype):
    got, want = (readings(form, dtype)[path][0] for path in ("pallas", "jnp"))
    assert len(got) == len(want) == 3
    for (width, _, _), a, b in zip(FORMS[form][2], got, want):
        assert a.shape == b.shape == (BATCH, SEQ, width)
        assert a.dtype == b.dtype == jnp.dtype(dtype)
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if dtype == "float32":
            np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)
        else:                   # to one unit in the last place of bfloat16
            assert bool(jnp.all(jnp.abs(a - b) <= 2.0 ** -7 * jnp.abs(b)))


@pytest.mark.parametrize("form,dtype", CASES)
def test_the_gradients_are_the_jnp_paths(form, dtype):
    got, want = (readings(form, dtype)[path][1] for path in ("pallas", "jnp"))
    assert len(got) == len(want) == 2 + FORMS[form][3]
    # bfloat16: the jnp path rounds the cotangent between the
    # normalisation and the SiLU to the activations' type, the kernel keeps
    # it float32
    limit = 1e-5 if dtype == "float32" else 1e-2
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert rel(a, b) < limit


@pytest.mark.parametrize("form,dtype", CASES)
def test_columns_outside_the_parts_get_no_gradient(form, dtype):
    width, offset, parts, _ = FORMS[form]
    dproj = readings(form, dtype)["pallas"][1][0]
    inside = sum(p[0] for p in parts)
    assert offset + inside < width
    assert not bool(jnp.any(dproj[..., :offset]))
    assert not bool(jnp.any(dproj[..., offset + inside:]))
    assert bool(jnp.any(dproj[..., offset:offset + inside]))


@pytest.mark.parametrize("form", FORMS)
def test_the_rows_in_front_and_behind_cross_block_boundaries(form,
                                                             monkeypatch):
    """A row cut into three blocks, a row in one block of three spans and a
    row in one block and one span read the same: the rows in front of a
    block or span (forward) and the pre-activation's cotangent of the rows
    behind it (backward) are what a whole row has there."""
    _, offset, parts, _ = FORMS[form]
    proj, taps, bias = inputs(form, jnp.float32, seed=2)

    def both(tokens, span):
        monkeypatch.setattr(K, "_BLOCK_TOKENS", tokens)
        monkeypatch.setattr(K, "_SPAN", span)
        K._forward.clear_cache()        # the span is read inside the
        K._backward.clear_cache()       # jitted builders
        fn = lambda p, w: K.conv_split(p, w, bias, offset, parts, True, True)
        loss = lambda p, w: sum(jnp.sum(o * o) for o in fn(p, w))
        return fn(proj, taps), jax.grad(loss, argnums=(0, 1))(proj, taps)

    assert SEQ // K._tile(BLOCK, K._HALO, SEQ) == 3
    assert K._spans(SEQ) == (range(0, SEQ, SEQ), SEQ)
    try:
        cut, whole = both(BLOCK, 128), both(512, 128)
        monkeypatch.setattr(K, "_SPAN", 32)
        assert len(K._spans(SEQ)[0]) == 3
        spans = both(512, 32)
    finally:
        K._forward.clear_cache()
        K._backward.clear_cache()
    for other in (cut, spans):
        for a, b in zip(jax.tree_util.tree_leaves(other),
                        jax.tree_util.tree_leaves(whole)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # ... and a block that saw zeros in front of it would read otherwise
    alone = K.conv_split(proj[:, BLOCK:], taps, bias, offset, parts, True,
                         True)
    assert rel(alone[0][:, :TAPS - 1], cut[0][0][:, BLOCK:BLOCK + TAPS - 1]
               ) > 1e-2
    np.testing.assert_allclose(alone[0][:, TAPS - 1:],
                               cut[0][0][:, BLOCK + TAPS - 1:], rtol=1e-5,
                               atol=1e-5)


def test_a_recomputed_block_keeps_the_projection_alone():
    """Forward, the block's second forward and the backward: a launch a
    part each, and between them no array but the projection's buffer, the
    taps and the parts' cotangents (no float32 pre-activation is kept)."""
    _, offset, parts, _ = FORMS["delta-net"]
    proj, taps, _ = inputs("delta-net", jnp.bfloat16)
    # what reads the parts (here a square) wants them again in the backward
    loss = lambda p, w: sum(jnp.sum(o.astype(jnp.float32) ** 2) for o in
                            K.conv_split(p, w, None, offset, parts, True,
                                         True))
    text = str(jax.make_jaxpr(jax.value_and_grad(
        jax.checkpoint(loss), argnums=(0, 1)))(proj, taps))
    calls = [line for line in text.splitlines() if " = pallas_call[" in line]
    names = re.findall(r"name=(causal_conv_\w+)", text)
    assert len(calls) == len(names) == 9
    assert sorted(names) == ["causal_conv_bwd"] * 3 + ["causal_conv_fwd"] * 6
    wide = "f32[%d,%d," % (BATCH, SEQ)
    assert not [line for line in calls if wide in line.split(" = ")[0]]


SHAPE = dict(seq=SEQ, width=1024, offset=256,
             parts=((256, 128, 1.0), (128, None, 1.0)), taps=TAPS)


@pytest.mark.parametrize("change,takes", [
    ({}, True),
    ({"offset": 0}, True),
    ({"offset": 192}, False),                       # off a lane tile
    ({"parts": ((256, 128, 1.0), (192, None, 1.0))}, False),
    ({"parts": ((256, 64, 1.0), (128, None, 1.0))}, False),   # half a tile
    ({"parts": ((256, 256, 1.0), (128, None, 1.0))}, True),   # two tiles
    ({"parts": ((128, None, 1.0), (256, 256, 1.0))}, False),  # starts inside
    ({"width": 512}, False),                        # parts past the buffer
    ({"seq": 104}, False),                          # no whole 16-row tiles
    ({"taps": 9}, False),
    ({"taps": 1}, True),
])
def test_supported_is_a_rule_on_shapes_and_backend(monkeypatch, change,
                                                   takes):
    shape = {**SHAPE, **change}
    assert K.supported(**shape) is False            # a CPU, no scope
    assert K.supported(**shape, interpret=True) is takes
    with fa.interpret_scope():
        assert K.supported(**shape) is takes
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert K.supported(**shape) is takes


def conv_calls():
    from paddle_tpu.observability import registry
    snap = registry.default_registry().snapshot().get("ssm.conv_calls")
    return {s["labels"]["path"]: s["value"]
            for s in (snap or {"series": []})["series"]}


@pytest.mark.parametrize("interpreted,offset,path", [
    (True, 256, "pallas"),
    (False, 256, "jnp"),                            # a CPU
    (True, 192, "jnp"),                             # off a lane tile
])
def test_the_counter_names_the_path(interpreted, offset, path):
    rng = np.random.default_rng(0)
    parts = ((256, 128, 0.5), (128, None, 1.0))
    proj = jnp.asarray(rng.normal(0, 1, (1, 32, 1024)), jnp.float32)
    taps = jnp.asarray(rng.uniform(-0.5, 0.5, (TAPS, 384)), jnp.float32)
    before = conv_calls()
    if interpreted:
        with fa.interpret_scope():
            got = FS.conv_split_raw(proj, offset, parts, taps)
    else:
        got = FS.conv_split_raw(proj, offset, parts, taps)
    after = conv_calls()
    assert {k: after.get(k, 0) - before.get(k, 0)
            for k in ("pallas", "jnp")} == {"pallas": int(path == "pallas"),
                                            "jnp": int(path == "jnp")}
    from paddle_tpu.nn.functional.linear_attn import l2_normalize_raw
    conv = FS.causal_conv1d_raw(proj[..., offset:offset + 384], taps)
    want = (l2_normalize_raw(conv[..., :256].reshape(1, 32, 2, 128),
                             scale=0.5).reshape(1, 32, 256), conv[..., 256:])
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)
