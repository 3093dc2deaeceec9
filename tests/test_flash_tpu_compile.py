"""The flash kernels of the main path, compiled for a described v5e at the
benchmark's real widths — no chip needed, about ten seconds each.  The
Pallas interpreter accepts what Mosaic refuses (a (1, t) store at a lane
offset into a dynamically indexed row, more VMEM than a kernel may use):
PR 26's sub-tiled band met both only here.  One file, topology inside a
fixture (only one process may hold the TPU library; see the
on-chip-measurement guide)."""
import re

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu.kernels.flash_attention_pallas as fap


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


# (b, s, h, d, the backward's path, resident forward?)
CELLS = [
    pytest.param(16, 1024, 16, 64, "resident", True, id="gpt2-medium-s1024"),
    pytest.param(4, 2048, 16, 128, "resident", True,
                 id="cerebras-1.3b-s2048"),
    pytest.param(1, 16384, 16, 64, "split", False,
                 id="gpt2-medium-s16384-streamed"),
    pytest.param(1, 4096, 16, 64, "merged", True, id="gpt2-medium-s4096"),
    pytest.param(4, 2048, 8, 128, "resident", True,
                 id="cerebras-1.3b-s2048-mp2-shard"),
    pytest.param(1, 16384, 16, 256, "split", False,
                 id="qwen3-next-s16384-head256"),
]


def _specs(b, s, h, d, causal, dtype=jnp.bfloat16):
    hg_b = fap._pick_head_group(h, d, s)
    hg_f = fap._pick_fwd_head_group(h, d, s, hg_b)
    bq, bk = fap._prep_blocks(s, s, causal, fap.DEFAULT_BLOCK_Q,
                              fap.DEFAULT_BLOCK_K, "test")
    return hg_f, fap._resolve_specs(b, s, s, h, d, dtype, causal, bq, bk,
                                    hg_f, hg_b, use_autotune=False)


def _compiled_text(fn, *args):
    # the suite asks for f32 matmul passes (conftest); the chip runs the
    # default precision, and Mosaic has no f32 pass over bf16 operands
    with jax.default_matmul_precision("default"):
        return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("b,s,h,d,path,short", CELLS)
def test_causal_flash_compiles_for_v5e(one_chip, no_persistent_cache, b, s,
                                       h, d, path, short):
    hg_f, (fwd_spec, bwd_spec) = _specs(b, s, h, d, True)
    assert bwd_spec[0] == path
    assert fap._kv_fits_resident(s, hg_f * d) == short
    scale = 1.0 / d ** 0.5

    def step(q, k, v, do):
        out, lse = fap._flash_fwd(q, k, v, True, scale, d, False, fwd_spec)
        return fap._flash_bwd(q, k, v, out, lse, do, True, scale, d, False,
                              bwd_spec)

    x = jax.ShapeDtypeStruct((b, s, h * d), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(step, x, x, x, x)
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (3 if path == "split" else 2)
    # delta: the merged and split kernels are handed it (an f32 product of
    # dO and O in XLA), the resident one forms it from O
    assert ("f32[%d,%d,%d]" % (b, s, h * d) in text) == (path != "resident")


# what else the resident backward is asked for: (b, s, h, d, causal,
# dtype, lse cotangent?) — full attention (every block pair live), the
# ring-attention inner's lse cotangent rows, float32 operands at the
# largest shape the rule admits (twice the bytes it plans with)
RESIDENT = [
    pytest.param(16, 1024, 16, 64, False, jnp.bfloat16, False, id="full"),
    pytest.param(4, 2048, 16, 128, False, jnp.bfloat16, False,
                 id="full-s2048-d128"),
    pytest.param(16, 1024, 16, 64, True, jnp.bfloat16, True, id="dlse"),
    pytest.param(2, 2048, 16, 128, True, jnp.float32, True,
                 id="float32-dlse-s2048-d128"),
]


@pytest.mark.parametrize("b,s,h,d,causal,dtype,with_dlse", RESIDENT)
def test_resident_backward_compiles_for_v5e(one_chip, no_persistent_cache,
                                            b, s, h, d, causal, dtype,
                                            with_dlse):
    _, (_, bwd_spec) = _specs(b, s, h, d, causal, dtype)
    assert bwd_spec[0] == "resident"
    scale = 1.0 / d ** 0.5

    def bwd(q, k, v, do, out, lse, *dlse):
        return fap._flash_bwd(q, k, v, out, lse, do, causal, scale, d,
                              False, bwd_spec, dlse=dlse[0] if dlse else None)

    x = jax.ShapeDtypeStruct((b, s, h * d), dtype, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((b, h, s), jnp.float32, sharding=one_chip)
    text = _compiled_text(bwd, x, x, x, x, x, rows,
                          *([rows] if with_dlse else []))
    assert text.count('custom_call_target="tpu_custom_call"') == 1


# ---------------------------------------------------------------------------
# one attention layer around the packed kernels (PR 32): what XLA puts
# between the fused projection's GEMMs and the two custom calls
# ---------------------------------------------------------------------------

def _balanced(text, start):
    """Index just past the parenthesis group that opens at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if depth == 0:
            return i + 1
    raise ValueError(text[start:start + 80])


def _entry_instructions(text):
    """The ENTRY computation of a compiled module's text as ``{name: (type
    without layouts, opcode, [operand names], attributes)}``."""
    import re
    body = text[text.index("\nENTRY "):]
    out = {}
    for line in body[:body.index("\n}")].splitlines()[2:]:
        name, _, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        end = _balanced(rest, 0) if rest[0] == "(" else rest.index(" ")
        kind = re.sub(r"\{[^{}]*\}", "", rest[:end])
        rest = rest[end:].lstrip()
        open_ = rest.index("(")
        close = _balanced(rest, open_)
        out[name.lstrip("%")] = (
            kind, rest[:open_],
            re.findall(r"%([\w.\-]+)", rest[open_:close]), rest[close:])
    return out


def _through_async_copies(ins, name):
    """``name``, or what an asynchronous copy between memory spaces that
    ends in ``name`` began from (the compiler's own placement: no pass of
    the program's)."""
    while ins[name][1] == "copy-done":
        name = ins[ins[name][2][0]][2][0]
    return name


def _attention_layer(packed, b, s, h, d):
    """qkv GEMM + bias, causal flash attention (the packed entry, or the
    three last-dimension slices ``models/gpt.py`` takes), output GEMM."""
    hd = h * d

    def layer(x, w_qkv, b_qkv, w_out):
        qkv = jnp.einsum("bsh,hk->bsk", x, w_qkv) + b_qkv
        if packed:
            out = fap.flash_attention_packed_native(qkv, h, causal=True,
                                                    interpret=False)
        else:
            out = fap.flash_attention_bshd_native(
                *(qkv[:, :, i * hd:(i + 1) * hd].reshape(b, s, h, d)
                  for i in range(3)), causal=True, interpret=False)
        y = jnp.einsum("bsh,hk->bsk", out.reshape(b, s, hd), w_out)
        return jnp.sum(y.astype(jnp.float32) ** 2)
    return jax.grad(layer, argnums=(0, 1, 2, 3))


# (b, s, h, d, packed?): the benchmark's cell and PR 29's proof shape; the
# sliced layer at the cell's shape is the control: the pass the packed
# operands remove is there, and this test sees it
LAYERS = [
    pytest.param(16, 1024, 16, 64, True, id="gpt2-medium-s1024"),
    pytest.param(2, 2048, 16, 128, True, id="cerebras-1.3b-s2048"),
    pytest.param(16, 1024, 16, 64, False, id="gpt2-medium-s1024-sliced"),
]


@pytest.mark.parametrize("b,s,h,d,packed", LAYERS)
def test_no_pass_between_the_projection_and_the_packed_kernels(
        one_chip, no_persistent_cache, b, s, h, d, packed):
    hd = h * d
    wide = "bf16[%d,%d,%d]" % (b, s, 3 * hd)
    part = "bf16[%d,%d,%d]" % (b, s, hd)
    shapes = [(b, s, hd), (hd, 3 * hd), (3 * hd,), (hd, hd)]
    ins = _entry_instructions(_compiled_text(
        _attention_layer(packed, b, s, h, d),
        *(jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
          for shape in shapes)))

    def is_kernel(name):
        return ins[name][1] == "custom-call" and \
            'custom_call_target="tpu_custom_call"' in ins[name][3]

    def kind(name):
        return "kLoop" if "kind=kLoop" in ins[name][3] else \
            "kOutput" if "kind=kOutput" in ins[name][3] else None

    def consumers(producer):
        return [n for n, (_, op, operands, _) in ins.items()
                if op not in ("copy-start", "copy-done") and producer in
                {_through_async_copies(ins, o) for o in operands}]

    kernels = [n for n in ins if is_kernel(n)]
    assert len(kernels) == 2
    # the projection's output is ONE buffer, written by the GEMM's fusion
    (gemm,) = [n for n, (t, op, _, _) in ins.items()
               if t == wide and op != "copy-done"]
    assert kind(gemm) == "kOutput"
    # every three-output split, concatenate or update-slice pass would be
    # a kLoop fusion that reads or writes the wide buffer or writes three
    # parts at once
    passes = [n for n, (t, op, operands, _) in ins.items()
              if kind(n) == "kLoop" and (
                  t == wide or t.count(part) >= 3 or
                  wide in {ins[o][0] for o in operands})]
    if not packed:
        # today's slices: one pass a layer, three written copies
        assert len(passes) == 1 and consumers(gemm) == passes
        assert ins[passes[0]][0] == "(%s)" % ", ".join([part] * 3)
        return
    assert passes == []
    # ... and its only consumers are the two kernels, three operands each
    assert sorted(consumers(gemm)) == sorted(kernels)
    for k in kernels:
        assert [_through_async_copies(ins, o)
                for o in ins[k][2][:3]] == [gemm] * 3
    # dq, dk and dv leave the backward as three arrays and enter the bias
    # reduce and BOTH gradient GEMMs of the projection as operands: the
    # pads fused away, no cotangent buffer of the wide shape exists
    (bwd,) = [k for k in kernels if ins[k][0] == "(%s)" % ", ".join(
        [part] * 3)]
    grads = {n for n, (_, op, operands, _) in ins.items()
             if op == "get-tuple-element" and operands == [bwd]}
    assert len(grads) == 3
    takers = {}
    for n in ins:
        if grads <= {_through_async_copies(ins, o) for o in ins[n][2]}:
            takers[ins[n][0]] = kind(n)
    assert takers == {"bf16[%d]" % (3 * hd): "kLoop",           # the bias
                      "bf16[%d,%d]" % (hd, 3 * hd): "kOutput",  # the weight
                      part: "kOutput"}                          # the input
    for g in grads:
        assert len(consumers(g)) == 3


@pytest.mark.usefixtures("no_persistent_cache")
@pytest.mark.parametrize("rows", [9216, 49152])
def test_grouped_products_compile_at_the_expert_cells_shapes(one_chip, rows):
    """The megablox kernels behind ``kernels.grouped_matmul`` at the widths
    of ``train_nemo3nano_s8192`` (8 experts, 2,688 -> 1,856 -> 2,688; the
    usual launch and the worst case), forward and both gradients: Mosaic
    takes the 896- and 640-wide tiles, the irregular last tile of 1,856 and
    the masks around the calls."""
    from paddle_tpu.kernels import grouped_matmul as gm

    def two(xs, w_up, w_down, sizes):
        # ``grouped_matmul`` asks the backend, which is a CPU here: the
        # kernels' builder is called directly, as the guide says to
        up = gm._gmm(xs, w_up, sizes, jnp.bfloat16, 512, False)
        return jnp.sum(gm._gmm(jnp.square(jax.nn.relu(up)), w_down, sizes,
                               jnp.float32, 512, False))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = _compiled_text(
        jax.grad(two, argnums=(0, 1, 2)),
        spec((rows, 2688), jnp.bfloat16), spec((8, 2688, 1856), jnp.bfloat16),
        spec((8, 1856, 2688), jnp.bfloat16), spec((8,), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') >= 5
    assert gm._tile(2688) == 896 and gm._tile(1856) == 640


@pytest.mark.usefixtures("no_persistent_cache")
@pytest.mark.parametrize("rows", [30720, 163840])
def test_gated_grouped_products_compile_at_the_narrow_experts_shapes(
        one_chip, rows):
    """The same kernels at the widths of ``train_qwen3next_s16384`` (32
    gated experts, 2,048 -> 512 twice and 512 -> 2,048; the usual launch of
    30,720 rows and the dropless worst case of 163,840), forward and every
    gradient: nine Mosaic calls, the axes of 512 one tile each."""
    from paddle_tpu.kernels import grouped_matmul as gm

    def three(xs, w_gate, w_up, w_down, sizes):
        gate = gm._gmm(xs, w_gate, sizes, jnp.bfloat16, 512, False)
        up = gm._gmm(xs, w_up, sizes, jnp.bfloat16, 512, False)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(jnp.bfloat16)
        return jnp.sum(gm._gmm(act, w_down, sizes, jnp.float32, 512, False))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    wide = spec((32, 2048, 512), jnp.bfloat16)
    text = _compiled_text(
        jax.grad(three, argnums=(0, 1, 2, 3)),
        spec((rows, 2048), jnp.bfloat16), wide, wide,
        spec((32, 512, 2048), jnp.bfloat16), spec((32,), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') >= 8
    assert gm._tile(2048) == 1024 and gm._tile(512) == 512
    assert gm._row_tile(rows) == 512


# ---------------------------------------------------------------------------
# the Mamba-2 scan's kernels at the hybrid cell's shapes: 1 x 8,192 tokens,
# 64 heads of 64, 8 groups of state 128, chunk 128, bf16

SCAN = dict(b=1, s=8192, h=64, p=64, g=8, n=128, chunk=128, hidden=2688)


def _scan_specs(one_chip):
    c = SCAN
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    return (spec((c["b"], c["s"], c["h"], c["p"]), jnp.bfloat16),
            spec((c["b"], c["s"], c["h"]), jnp.float32),
            spec((c["h"],), jnp.float32),
            spec((c["b"], c["s"], c["g"], c["n"]), jnp.bfloat16),
            spec((c["b"], c["s"], c["g"], c["n"]), jnp.bfloat16),
            spec((c["h"],), jnp.float32))


@pytest.mark.usefixtures("no_persistent_cache")
@pytest.mark.parametrize("way", ["forward", "backward"])
def test_scan_kernels_compile_at_the_hybrid_cells_shapes(one_chip, way):
    """``ssd_scan_raw`` asks the backend, which is a CPU here: the
    ``custom_vjp`` under it is called directly.  Mosaic takes the (R, L)
    blocks it turns over, the lane masks of two heads a tile, the
    one-row stores and the transposed products."""
    from paddle_tpu.kernels import ssd_scan as ks
    assert ks.supported(SCAN["chunk"], SCAN["h"] // SCAN["g"], SCAN["p"],
                        SCAN["n"], interpret=True)

    def scan(*args):
        return ks.ssd_scan(*args, SCAN["chunk"], False)

    fn = scan if way == "forward" else jax.grad(
        lambda *a: jnp.sum(scan(*a).astype(jnp.float32)), argnums=range(6))
    text = _compiled_text(fn, *_scan_specs(one_chip))
    kernels = re.findall(r"%(ssd_scan_\w+?)[.\d]* = ", text)
    assert sorted(set(kernels)) == (
        ["ssd_scan_fwd"] if way == "forward" else
        ["ssd_scan_bwd", "ssd_scan_fwd"])
    assert text.count('custom_call_target="tpu_custom_call"') == \
        len(kernels) == (1 if way == "forward" else 2)


def _mixer_layer():
    """``Mamba2Mixer`` as ``models/nemotron_h.py`` writes it (projection,
    convolution, scan, gated norm, projection) under its two roles, a
    recomputed block as the cell runs it, loss and gradients."""
    from paddle_tpu.kernels import ssd_scan as ks
    from paddle_tpu.nn.functional import ssm as fs
    from paddle_tpu.observability import scopes
    c = SCAN
    inner = c["h"] * c["p"]
    conv = inner + 2 * c["g"] * c["n"]

    def mixer(u, w_in, conv_w, conv_b, a_log, dt_bias, d, norm_w, w_out):
        with scopes.scope(scopes.SSM):
            proj = jnp.einsum("bsh,hk->bsk", u, w_in)
            z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + conv],
                          proj[..., inner + conv:])
            xbc = fs.causal_conv1d_raw(xbc, conv_w, conv_b, silu=True)
            x = xbc[..., :inner].reshape(c["b"], c["s"], c["h"], c["p"])
            bm, cm = (xbc[..., inner + i * c["g"] * c["n"]:
                          inner + (i + 1) * c["g"] * c["n"]].reshape(
                              c["b"], c["s"], c["g"], c["n"])
                      for i in range(2))
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            with scopes.scope(scopes.SSM_SCAN):
                y = ks.ssd_scan(x, dt, -jnp.exp(a_log), bm, cm, d,
                                c["chunk"], False)
            y = fs.gated_group_rms_norm_raw(
                y.reshape(c["b"], c["s"], inner), z, norm_w, c["g"], 1e-5)
            out = jnp.einsum("bsk,kh->bsh", y, w_out)
            return jnp.sum(out.astype(jnp.float32) ** 2)

    shapes = [((c["b"], c["s"], c["hidden"]), jnp.bfloat16),
              ((c["hidden"], inner + conv + c["h"]), jnp.bfloat16),
              ((4, conv), jnp.bfloat16), ((conv,), jnp.bfloat16),
              ((c["h"],), jnp.float32), ((c["h"],), jnp.float32),
              ((c["h"],), jnp.float32), ((inner,), jnp.float32),
              ((inner, c["hidden"]), jnp.bfloat16)]
    return jax.value_and_grad(jax.checkpoint(mixer),
                              argnums=tuple(range(9))), shapes


@pytest.mark.usefixtures("no_persistent_cache")
def test_the_mixer_layer_keeps_its_scan_in_the_kernels(one_chip):
    """Forward, the block's recomputation and the backward: three Mosaic
    calls under ``ssm_scan``; the first writes y alone, the second the
    entering states beside it (134 MB of float32); under that role no
    matrix product is XLA's and no float32 buffer has the 268 MB of a
    layer's (chunk, chunk) matrices."""
    fn, shapes = _mixer_layer()
    text = _compiled_text(fn, *(jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                                for s, d in shapes))
    under = [line for line in text.splitlines()
             if re.search(r'op_name="[^"]*\bssm_scan\b', line)]
    calls = [line for line in under
             if 'custom_call_target="tpu_custom_call"' in line]
    names = sorted(re.match(r"\s*%([a-z_]+)", line).group(1)
                   for line in calls)
    assert names == ["ssd_scan_bwd", "ssd_scan_fwd", "ssd_scan_fwd"]
    assert len(calls) == text.count('custom_call_target="tpu_custom_call"')
    c = SCAN
    states = "f32[%d,%d,%d,%d,%d]" % (
        c["b"], c["g"], c["s"] // c["chunk"], c["n"],
        c["h"] // c["g"] * c["p"])
    head = lambda line: line.split(" custom-call(")[0]
    assert sorted(states in head(line) for line in calls
                  if "%ssd_scan_fwd" in line) == [False, True]
    assert not [line for line in under
                if re.search(r" (dot|convolution)\(", line)]
    matrices = 4 * c["s"] // c["chunk"] * c["h"] * c["chunk"] ** 2
    for line in under:
        for dims in re.findall(r"f32\[([\d,]+)\]", head(line)
                               if line in calls else line.split(" = ")[1]
                               .split("(")[0]):
            size = 4
            for dim in dims.split(","):
                size *= int(dim)
            assert size < matrices, line[:200]


# ---------------------------------------------------------------------------
# the gated delta rule's kernels at the linear-attention cell's shapes:
# 1 x 16,384 tokens, 16 key and 32 value heads of 128, chunk 64, bf16

RULE = dict(b=1, s=16384, hk=16, hv=32, d=128, chunk=64, hidden=2048)


def _rule_specs(one_chip):
    c = RULE
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    return (spec((c["b"], c["s"], c["hk"], c["d"]), jnp.bfloat16),
            spec((c["b"], c["s"], c["hk"], c["d"]), jnp.bfloat16),
            spec((c["b"], c["s"], c["hv"], c["d"]), jnp.bfloat16),
            spec((c["b"], c["s"], c["hv"]), jnp.float32),
            spec((c["b"], c["s"], c["hv"]), jnp.float32))


@pytest.mark.usefixtures("no_persistent_cache")
@pytest.mark.parametrize("way", ["forward", "backward"])
def test_delta_rule_kernels_compile_at_the_linear_attention_cells_shapes(
        one_chip, way):
    """``gated_delta_rule_raw`` asks the backend, which is a CPU here: the
    ``custom_vjp`` under it is called directly.  Mosaic takes the (8, 128)
    tile of per-token vectors it turns over, the float32 products of the
    block substitution, the one-row stores and the transposed products."""
    from paddle_tpu.kernels import delta_rule as kd
    assert kd.supported(RULE["chunk"], RULE["hv"] // RULE["hk"], RULE["d"],
                        RULE["d"], interpret=True)

    def rule(*args):
        return kd.delta_rule(*args, RULE["chunk"], False)

    fn = rule if way == "forward" else jax.grad(
        lambda *a: jnp.sum(rule(*a).astype(jnp.float32)), argnums=range(5))
    text = _compiled_text(fn, *_rule_specs(one_chip))
    kernels = re.findall(r"%(delta_rule_\w+?)[.\d]* = ", text)
    assert sorted(set(kernels)) == (
        ["delta_rule_fwd"] if way == "forward" else
        ["delta_rule_bwd", "delta_rule_fwd"])
    assert text.count('custom_call_target="tpu_custom_call"') == \
        len(kernels) == (1 if way == "forward" else 2)


def _delta_net_layer():
    """``GatedDeltaNet`` as ``models/qwen3_next.py`` writes it (projections,
    convolution, normalisations, gates, the rule, gated norm, projection)
    under its two roles, a recomputed layer as the cell runs it, loss and
    gradients."""
    import math
    from paddle_tpu.kernels import delta_rule as kd
    from paddle_tpu.nn.functional import linear_attn as fl
    from paddle_tpu.nn.functional import ssm as fs
    from paddle_tpu.observability import scopes
    c = RULE
    key_dim, value_dim = c["hk"] * c["d"], c["hv"] * c["d"]

    def layer(x, w_qkvz, w_ba, conv_w, a_log, dt_bias, w_out):
        with scopes.scope(scopes.LINEAR_ATTN):
            qkvz = jnp.einsum("bsh,hk->bsk", x, w_qkvz)
            ba = jnp.einsum("bsh,hk->bsk", x, w_ba).astype(jnp.float32)
            qkv = fs.causal_conv1d_raw(qkvz[..., :2 * key_dim + value_dim],
                                       conv_w, silu=True)
            z = qkvz[..., 2 * key_dim + value_dim:]
            q = fl.l2_normalize_raw(
                qkv[..., :key_dim].reshape(c["b"], c["s"], c["hk"], c["d"]),
                scale=1.0 / math.sqrt(c["d"]))
            k = fl.l2_normalize_raw(qkv[..., key_dim:2 * key_dim].reshape(
                c["b"], c["s"], c["hk"], c["d"]))
            v = qkv[..., 2 * key_dim:].reshape(c["b"], c["s"], c["hv"],
                                               c["d"])
            beta = jax.nn.sigmoid(ba[..., :c["hv"]])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., c["hv"]:] + dt_bias)
            with scopes.scope(scopes.LINEAR_ATTN_SCAN):
                o = kd.delta_rule(q, k, v, g, beta, c["chunk"], False)
            gated = (o.reshape(c["b"], c["s"], value_dim).astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32))).astype(o.dtype)
            out = jnp.einsum("bsk,kh->bsh", gated, w_out)
            return jnp.sum(out.astype(jnp.float32) ** 2)

    shapes = [((c["b"], c["s"], c["hidden"]), jnp.bfloat16),
              ((c["hidden"], 2 * key_dim + 2 * value_dim), jnp.bfloat16),
              ((c["hidden"], 2 * c["hv"]), jnp.bfloat16),
              ((4, 2 * key_dim + value_dim), jnp.bfloat16),
              ((c["hv"],), jnp.float32), ((c["hv"],), jnp.float32),
              ((value_dim, c["hidden"]), jnp.bfloat16)]
    return jax.value_and_grad(jax.checkpoint(layer),
                              argnums=tuple(range(7))), shapes


@pytest.mark.usefixtures("no_persistent_cache")
def test_the_delta_net_layer_keeps_its_rule_in_the_kernels(one_chip):
    """Forward, the layer's recomputation and the backward: three Mosaic
    calls under ``linear_attn_scan``; the first writes o alone, the second
    the states entering its grid steps beside it (128 MiB of float32); under that role no
    ``while`` (the ``jnp`` path's ``lax.scan`` and head groups), no matrix
    product of XLA's and no float32 buffer of the size of a layer's (C, C)
    matrices (the decays, systems and inverses of the ``jnp`` path: 128
    MiB each) but those states, nor any (..., 64, 64) one."""
    fn, shapes = _delta_net_layer()
    text = _compiled_text(fn, *(jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                                for s, d in shapes))
    under = [line for line in text.splitlines()
             if re.search(r'op_name="[^"]*\blinear_attn_scan\b', line)]
    calls = [line for line in under
             if 'custom_call_target="tpu_custom_call"' in line]
    names = sorted(re.match(r"\s*%([a-z_]+)", line).group(1)
                   for line in calls)
    assert names == ["delta_rule_bwd", "delta_rule_fwd", "delta_rule_fwd"]
    assert len(calls) == text.count('custom_call_target="tpu_custom_call"')
    c = RULE
    states = "f32[%d,%d,%d,%d,%d]" % (     # a grid step takes 256 tokens
        c["b"], c["hk"], c["s"] // 256, c["d"], c["hv"] // c["hk"] * c["d"])
    head = lambda line: line.split(" custom-call(")[0]
    assert sorted(states in head(line) for line in calls
                  if "%delta_rule_fwd" in line) == [False, True]
    assert not [line for line in under
                if re.search(r" (dot|convolution|while)\(", line)]
    matrices = 4 * c["s"] // c["chunk"] * c["hv"] * c["chunk"] ** 2
    for line in under:
        if line in calls:
            continue
        for dims in re.findall(r"f32\[([\d,]+)\]",
                               line.split(" = ")[1].split("(")[0]):
            size = 4
            for dim in dims.split(","):
                size *= int(dim)
            assert size < matrices or "f32[%s]" % dims == states, line[:200]
            assert not dims.endswith(",%d,%d" % (c["chunk"], c["chunk"]))


# ---------------------------------------------------------------------------
# what stands in front of the scans (PR 38): the convolution's kernels at the
# two recurrent cells' shapes, and a mixer of each family around them

# (tokens, the projection's width, the convolved columns' offset, parts,
# bias?): ``models/nemotron_h.py``'s [z | x B C | dt] and
# ``models/qwen3_next.py``'s [q k v | z]
CONV = {
    "nemo3nano": (8192, 10304, 4096,
                  ((4096, None, 1.0), (1024, None, 1.0), (1024, None, 1.0)),
                  True),
    "qwen3next": (16384, 12288, 0,
                  ((2048, 128, 128 ** -0.5), (2048, 128, 1.0),
                   (4096, None, 1.0)), False),
}


@pytest.mark.usefixtures("no_persistent_cache")
@pytest.mark.parametrize("way", ["forward", "backward"])
@pytest.mark.parametrize("cell", CONV)
def test_conv_kernels_compile_at_the_recurrent_cells_shapes(one_chip, cell,
                                                            way):
    """``conv_split_raw`` asks the backend, which is a CPU here: the
    ``custom_vjp`` under it is called directly.  Mosaic takes the channel
    tiles picked out of a buffer of 80.5 lane tiles, the 16-row block in
    front, the sublane rotations, the one-row accumulator stores and the
    reversed token axis."""
    from paddle_tpu.kernels import causal_conv as kc
    seq, width, offset, parts, bias = CONV[cell]
    assert kc.supported(seq, width, offset, parts, 4, interpret=True)
    channels = sum(p[0] for p in parts)
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                               sharding=one_chip)
    args = [spec(1, seq, width), spec(4, channels)] + [spec(channels)] * bias

    def split(proj, taps, b=None):
        return kc.conv_split(proj, taps, b, offset, parts, True, False)

    fn = split if way == "forward" else jax.grad(
        lambda *a: sum(jnp.sum(o.astype(jnp.float32) ** 2)
                       for o in split(*a)), argnums=range(len(args)))
    text = _compiled_text(fn, *args)
    kernels = re.findall(r"%(causal_conv_\w+?)[.\d]* = ", text)
    assert sorted(kernels) == (["causal_conv_bwd"] * 3 if way == "backward"
                               else []) + ["causal_conv_fwd"] * 3
    assert text.count('custom_call_target="tpu_custom_call"') == len(kernels)


def _recurrent_mixer(cell):
    """(value_and_grad of a recomputed mixer built from the model's own
    class at the cell's widths, its arguments' shapes)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import functional_call
    if cell == "qwen3next":
        from paddle_tpu.models.qwen3_next import (GatedDeltaNet,
                                                  Qwen3NextConfig)
        layer, hidden = GatedDeltaNet(Qwen3NextConfig(
            num_hidden_layers=4)), 2048
    else:
        from paddle_tpu.models.nemotron_h import Mamba2Mixer, NemotronHConfig
        layer, hidden = Mamba2Mixer(NemotronHConfig()), 2688
    state = {name: (t._array.shape, jnp.float32 if getattr(
        t, "keep_fp32", False) else jnp.bfloat16)
        for name, t in layer.state_dict().items()}

    def loss(state, x):
        out, _ = functional_call(layer, state, paddle.Tensor(x))
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return (jax.value_and_grad(jax.checkpoint(loss), argnums=(0, 1)), state,
            ((1, CONV[cell][0], hidden), jnp.bfloat16))


@pytest.mark.usefixtures("no_persistent_cache")
@pytest.mark.parametrize("cell", CONV)
def test_no_pass_between_the_projection_and_the_scan(one_chip, monkeypatch,
                                                     cell):
    """``Mamba2Mixer`` / ``GatedDeltaNet`` themselves, a recomputed block,
    loss and gradients, dispatched as on a TPU.  Forward and second
    forward: the input projection's GEMM fusion writes ONE wide buffer,
    the convolution's kernels read it as it is and the scan's kernel reads
    what they write as it is.  Backward: the scan's backward kernel hands
    its cotangents to the convolution's, whose column gradients enter both
    gradient GEMMs of the projection as operands (no cotangent buffer of
    the wide shape exists).  Nowhere a float32 array of the convolved
    width, nor any array of a part's shape that a kernel did not write."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seq, width, _, parts, _ = CONV[cell]
    fn, state, (x_shape, x_dtype) = _recurrent_mixer(cell)
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    text = _compiled_text(fn, {k: spec(*v) for k, v in state.items()},
                          spec(x_shape, x_dtype))
    ins = _entry_instructions(text)

    def source(name):
        """``name`` behind the compiler's own plumbing."""
        while ins[name][1] in ("copy-done", "bitcast", "get-tuple-element"):
            name = (_through_async_copies(ins, name)
                    if ins[name][1] == "copy-done" else ins[name][2][0])
        return name

    def kernels(prefix):
        return [n for n in ins if n.startswith(prefix)
                and ins[n][1] == "custom-call"]

    def kind(name):
        return "kOutput" if "kind=kOutput" in ins[name][3] else \
            "kLoop" if "kind=kLoop" in ins[name][3] else None

    scan = "delta_rule" if cell == "qwen3next" else "ssd_scan"
    conv_fwd, conv_bwd = kernels("causal_conv_fwd"), kernels("causal_conv_bwd")
    scan_fwd, scan_bwd = kernels(scan + "_fwd"), kernels(scan + "_bwd")
    assert (len(conv_fwd), len(conv_bwd), len(scan_fwd), len(scan_bwd)) == (
        6, 3, 2, 1)
    wide = "bf16[1,%d,%d]" % (seq, width)
    gemms = [n for n, (t, op, _, _) in ins.items()
             if t == wide and op not in ("copy-done", "bitcast")]
    assert len(gemms) == 2 and {kind(g) for g in gemms} == {"kOutput"}
    # each projection's buffer goes into three kernels whole, twice each
    # (the block and the rows in front of it)
    for gemm in gemms:
        mine = [k for k in conv_fwd if source(ins[k][2][0]) == gemm]
        assert len(mine) == 3
        for k in mine:
            assert source(ins[k][2][1]) == gemm
    # ... and each scan reads three of those kernels' outputs as they are
    part_types = ["bf16[1,%d,%d]" % (seq, p[0]) for p in parts]
    for k in scan_fwd:
        feeds = [source(o) for o in ins[k][2][:3]]
        assert sorted(ins[f][0] for f in feeds) == sorted(part_types)
        assert set(feeds) <= set(conv_fwd) and len(set(feeds)) == 3
    # the backward: cotangents from the scan's kernel straight into the
    # convolution's, together with the second forward's projection
    for k in conv_bwd:
        assert source(ins[k][2][0]) == scan_bwd[0]
        assert source(ins[k][2][1]) in gemms
    # a convolution kernel's column gradient is read by the projection's
    # two gradient GEMMs and nothing else
    for k in conv_bwd:
        readers = [n for n, (_, op, operands, _) in ins.items()
                   if op == "fusion" and k in {source(o) for o in operands}]
        assert sorted(kind(r) for r in readers) == ["kOutput"] * 2
        weight = "bf16[%d,%d]" % (x_shape[2], width)
        (inputs,) = {ins[r][0] for r in readers} - {weight}
        assert inputs.endswith("%d,%d]" % (seq, x_shape[2]))
    # nothing else has the convolved width or the wide shape, and no
    # float32 array of the convolved width exists anywhere
    channels = sum(p[0] for p in parts)
    allowed = set(conv_fwd + conv_bwd + scan_fwd + scan_bwd + gemms)
    for n, (t, op, _, _) in ins.items():
        if op in ("get-tuple-element", "bitcast", "copy-start", "copy-done",
                  "parameter") or n in allowed:
            continue
        assert "[1,%d,%d]" % (seq, channels) not in t, (n, t)
        assert "[1,%d,%d]" % (seq + 3, channels) not in t, (n, t)
        assert wide not in t, (n, t)
    assert not re.findall(r"f32\[1,%d,%d\]" % (seq, channels), text)
