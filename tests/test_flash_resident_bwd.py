"""The resident flash backward (PR 30): one grid cell a (batch, head
group), whole-sequence blocks, O for delta, a Python-static walk over
block pairs.  Interpret-mode parity against the O(S^2) reference's
gradients and against the merged kernel's; the rule that places a shape
(`_bwd_plan`); the counter `flash.bwd_calls{path}` and the benchmark's
reader of it.  tests/test_flash_tpu_compile.py compiles the same kernel
for a described v5e."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.kernels.flash_attention_pallas as fap

def _operands(b, s, sk, h, d, seed):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, n, h * d), jnp.float32) * 0.5
                 for n in (s, sk, sk, s))


def _dense(q3, k3, v3, do3, h, d, causal, o3=None, dlse=None):
    """Gradients of dense softmax attention by the flash backward's own
    formulas, f32 throughout: dS = P * (dP - delta + dlse) with delta =
    rowsum(dO * O) — ``o3`` given (any array) or the attention's own."""
    b, s, _ = q3.shape
    sk = k3.shape[1]
    q, k, v, do = (x.reshape(b, -1, h, d) for x in (q3, k3, v3, do3))
    scale = 1.0 / d ** 0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        logits = jnp.where(jnp.tril(jnp.ones((s, sk), bool)), logits, -1e30)
    p = jax.nn.softmax(logits, -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v) if o3 is None else \
        o3.reshape(b, s, h, d)
    delta = jnp.einsum("bqhd,bqhd->bhq", do, o)
    if dlse is not None:
        delta = delta - dlse
    dp = jnp.einsum("bqhd,bkhd->bhqk", do, v)
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k) * scale
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q) * scale
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, do)
    return tuple(x.reshape(b, -1, h * d) for x in (dq, dk, dv))


def _backward(path, ops, h, d, causal, bq, bk, hg, variant="base", o3=None,
              dlse=None):
    """dq, dk, dv of ``_flash_bwd`` on ``path`` from the forward's own
    residuals (or a given ``o3``)."""
    q3, k3, v3, do3 = ops
    scale = 1.0 / d ** 0.5
    out, lse = fap._flash_fwd(q3, k3, v3, causal, scale, d, True,
                              ("base", bq, bk, hg))
    return fap._flash_bwd(q3, k3, v3, out if o3 is None else o3, lse, do3,
                          causal, scale, d, True,
                          (path, variant, bq, bk, hg), dlse=dlse)


def _close(got, want, tol, what):
    for name, a, w in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   err_msg="%s %s" % (what, name), **tol)


# (s, sk, h, d, block_q, block_k, hg): the benchmark's shape at both head
# groups, Cerebras-GPT's, a band of two key blocks, cross attention
SHAPES = [
    pytest.param(1024, 1024, 2, 64, 512, 512, 2, id="s1024-d64-hg2"),
    pytest.param(1024, 1024, 4, 64, 512, 512, 4, id="s1024-d64-hg4"),
    pytest.param(2048, 2048, 1, 128, 512, 512, 1, id="s2048-d128-hg1"),
    pytest.param(1024, 1024, 2, 64, 512, 256, 2, id="s1024-bq512-bk256"),
]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("s,sk,h,d,bq,bk,hg", SHAPES)
def test_resident_backward_matches_reference_and_merged(s, sk, h, d, bq, bk,
                                                        hg, causal):
    ops = _operands(1, s, sk, h, d, seed=s + d + hg)
    assert fap._resident_bwd_fits(s, sk, hg * d, causal, bq, bk)
    got = _backward("resident", ops, h, d, causal, bq, bk, hg)
    _close(got, _dense(*ops, h, d, causal), dict(atol=2e-4, rtol=1e-3),
           "against the reference")
    _close(got, _backward("merged", ops, h, d, causal, bq, bk, hg),
           dict(atol=2e-5, rtol=2e-4), "against the merged kernel")


@pytest.mark.parametrize("s,sk,h,d,bq,bk,hg", SHAPES)
def test_resident_backward_reads_packed_operands_in_place(s, sk, h, d, bq,
                                                          bk, hg):
    """q, k and v as the column parts of ONE (b, s, 3*h*d) buffer, passed
    three times and told apart by the block index maps (PR 32): the same
    kernel on the same bytes, so dq, dk and dv are the three-operand
    call's bit for bit — at this kernel's own head group (``n = h / hg``
    column blocks a part), whatever group the forward ran with."""
    q3, k3, v3, do3 = _operands(2, s, sk, h, d, seed=s + d + hg)
    qkv3 = jnp.concatenate([q3, k3, v3], axis=-1)
    scale = 1.0 / d ** 0.5
    hg_f = fap._pick_fwd_head_group(h, d, s, hg)
    out, lse = fap._flash_fwd(q3, k3, v3, True, scale, d, True,
                              ("base", bq, bk, hg_f))
    spec = ("resident", "base", bq, bk, hg)
    want = fap._flash_bwd(q3, k3, v3, out, lse, do3, True, scale, d, True,
                          spec)
    got = fap._flash_bwd(qkv3, qkv3, qkv3, out, lse, do3, True, scale, d,
                         True, spec, packed=True)
    for name, a, w in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == w.shape == (2, s, h * d), name
        assert bool(jnp.all(a == w)), name


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_resident_backward_under_bf16chain(causal):
    ops = _operands(1, 1024, 1024, 2, 64, seed=5)
    got = _backward("resident", ops, 2, 64, causal, 512, 512, 2,
                    variant="bf16chain")
    _close(got, _dense(*ops, 2, 64, causal), dict(atol=5e-2, rtol=5e-2),
           "against the reference")
    # the same chain in the same order on the same elements
    _close(got, _backward("merged", ops, 2, 64, causal, 512, 512, 2,
                          variant="bf16chain"),
           dict(atol=2e-5, rtol=2e-4), "against the merged kernel")


def test_resident_backward_of_cross_attention():
    """sk != s, non-causal: blocks of different sizes on the two axes."""
    s, sk, h, d = 256, 512, 2, 64
    ops = _operands(2, s, sk, h, d, seed=9)
    got = _backward("resident", ops, h, d, False, 128, 256, 2)
    _close(got, _dense(*ops, h, d, False), dict(atol=2e-4, rtol=1e-3),
           "against the reference")


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_resident_backward_takes_the_lse_cotangent_rows(causal):
    """dS = P * (dP - delta + dlse): the (b, h, s) rows ride beside the lse
    rows into the resident kernel, fold into delta in XLA for the merged."""
    s, h, d = 1024, 2, 64
    ops = _operands(1, s, s, h, d, seed=13)
    dlse = jnp.asarray(np.random.RandomState(14).randn(1, h, s),
                       jnp.float32) * 0.3
    got = _backward("resident", ops, h, d, causal, 512, 512, 2, dlse=dlse)
    _close(got, _dense(*ops, h, d, causal, dlse=dlse),
           dict(atol=2e-4, rtol=1e-3), "against the reference")
    _close(got, _backward("merged", ops, h, d, causal, 512, 512, 2,
                          dlse=dlse),
           dict(atol=2e-5, rtol=2e-4), "against the merged kernel")


@pytest.mark.parametrize("hg", [2, 4])
def test_the_kernels_delta_is_rowsum_of_do_times_o(hg):
    """Handed an O that is NOT the attention's output, the kernel's
    gradients follow delta = rowsum(dO * O) of THAT O (dq and dk read it;
    dv does not)."""
    s, h, d = 512, 4, 64
    ops = _operands(1, s, s, h, d, seed=21)
    o3 = jnp.asarray(np.random.RandomState(22).randn(1, s, h * d),
                     jnp.float32)
    got = _backward("resident", ops, h, d, True, 256, 256, hg, o3=o3)
    want = _dense(*ops, h, d, True, o3=o3)
    _close(got, want, dict(atol=2e-4, rtol=1e-3), "given O")
    own = _dense(*ops, h, d, True)
    assert float(jnp.max(jnp.abs(want[0] - own[0]))) > 1e-2   # O mattered
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(own[2]),
                               atol=2e-4, rtol=1e-3)


def test_with_lse_entry_differentiates_through_the_resident_kernel():
    """The ring-attention inner's entry at a shape the rule places on the
    resident backward, the loss consuming out and lse."""
    b, s, h, d = 1, 512, 2, 64
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(b, s, h, d), jnp.float32) * 0.5
               for _ in range(3))
    scale = 1.0 / np.sqrt(d)
    _, bwd_spec = fap._resolve_specs(b, s, s, h, d, q.dtype, True, 512, 512,
                                     2, 2, tie_groups=True)
    assert bwd_spec[0] == "resident"

    def loss_flash(q_, k_, v_):
        out, lse = fap.flash_attention_bshd_with_lse(
            q_, k_, v_, causal=True, interpret=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_ref(q_, k_, v_):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q_, k_) * scale
        logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -1e30)
        out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v_)
        lse = jnp.moveaxis(jax.scipy.special.logsumexp(logits, -1), 1, -1)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, w in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), rtol=2e-3,
                                   atol=2e-3)


# ---------------------------------------------------------------------------
# the rule: residency from (s, sk, h, d) and the blocks alone
# ---------------------------------------------------------------------------

def _plan(s, sk, h, d, causal, block_q=512, block_k=512):
    hg_b = fap._pick_head_group(h, d, max(s, sk))
    bq, bk = fap._prep_blocks(s, sk, causal, block_q, block_k, "test")
    hg_f = fap._pick_fwd_head_group(h, d, max(s, sk), hg_b)
    _, spec = fap._resolve_specs(1, s, sk, h, d, jnp.bfloat16, causal, bq,
                                 bk, hg_f, hg_b, use_autotune=False)
    return spec


@pytest.mark.parametrize("s,h,d,path,hg", [
    (1024, 16, 64, "resident", 2),        # the benchmark's cell
    (2048, 16, 128, "resident", 1),       # Cerebras-GPT 1.3B
    (2048, 8, 128, "resident", 1),        # ... 8 heads a shard (mp = 2)
    (256, 2, 64, "resident", 2),
    (4096, 16, 64, "merged", 4),          # 64 block pairs: too long a walk
    (8192, 16, 64, "merged", 2),
    (16384, 16, 64, "split", 4),
    (2048, 16, 256, "merged", 1),         # hg*d = 256: 20 MB of blocks
])
def test_the_shape_places_the_backward(s, h, d, path, hg):
    spec = _plan(s, s, h, d, True)
    assert spec[0] == path
    assert (spec[3] if path == "split" else spec[4]) == hg


@pytest.mark.parametrize("s,sk,h,d,causal,bq,bk,why", [
    (1024, 2048, 2, 64, True, 512, 512, "causal and not square"),
    (2048, 1024, 2, 64, True, 512, 512, "causal and not square"),
    (2048, 2048, 2, 64, True, 256, 128, "128 block pairs"),
    (2048, 2048, 2, 64, True, 512, 256, "32 block pairs"),
    (1024, 1024, 2, 64, True, 256, 512, "block_k over block_q"),
    (1024, 1024, 2, 64, False, 384, 512, "a ragged tail"),
    (4096, 4096, 1, 256, False, 2048, 2048, "a working set of 48 MB"),
])
def test_shapes_the_resident_backward_refuses(s, sk, h, d, causal, bq, bk,
                                              why):
    assert fap._resident_bwd_group(s, sk, h, d, causal, bq, bk) is None, why
    assert fap._bwd_plan(s, sk, h, d, causal, bq, bk, 1)[0] != "resident"


def test_a_refused_shape_still_differentiates():
    """Causal with sk > s: the merged kernel (zeroed scratch) gives the
    key blocks no score reaches their zeros; the resident walk would have
    had nothing to store there, which is why the rule refuses."""
    s, sk, h, d = 128, 256, 2, 64
    ops = _operands(1, s, sk, h, d, seed=31)
    assert _plan(s, sk, h, d, True, 128, 128)[0] == "merged"
    got = _backward("merged", ops, h, d, True, 128, 128, 2)
    _close(got, _dense(*ops, h, d, True), dict(atol=2e-4, rtol=1e-3),
           "against the reference")
    assert float(jnp.max(jnp.abs(got[1][:, s:]))) == 0.0


@pytest.mark.parametrize("config,want", [
    # a pinned group the path can hold is kept
    ({"block_q": 512, "block_k": 512, "hg": 4},
     ("resident", "bf16chain", 512, 512, 4)),
    # blocks that make the walk too long, a group whose blocks do not fit,
    # an unknown group: the default
    ({"block_q": 256, "block_k": 128, "hg": 2},
     ("resident", "base", 512, 512, 2)),
    ({"block_q": 1024, "block_k": 512, "hg": 4},
     ("resident", "base", 512, 512, 2)),
    ({"block_q": 512, "block_k": 512, "hg": 3},
     ("resident", "base", 512, 512, 2)),
])
def test_a_pinned_flash_bwd_candidate_resolves_or_falls_back(config, want):
    cand = {"variant": "bf16chain", "config": config}
    assert fap._sane_bwd_grouped("resident", cand, 1024, 1024, 16, 64, True,
                                 (512, 512, 2)) == want


def test_the_flash_bwd_familys_candidates_fit_their_path():
    key = fap.autotune_key(b=8, s=1024, sk=1024, h=16, d=64,
                           dtype="bfloat16", causal=True)
    assert fap._key_bwd_plan(key) == ("resident", 512, 512, 2)
    cands = fap._bwd_candidates_merged(key)
    assert cands[0] == {"variant": "base",
                        "config": {"block_q": 512, "block_k": 512, "hg": 2}}
    assert {c["config"]["hg"] for c in cands} == {2, 4}
    for c in cands:
        cfg = c["config"]
        assert fap._resident_bwd_fits(1024, 1024, cfg["hg"] * 64, True,
                                      cfg["block_q"], cfg["block_k"]), c
    # the family's traceable builds the kernel production runs
    fn, args = fap._bwd_traceable("merged")(cands[0], key)
    (eqn,) = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns]
    call = eqn.params["jaxpr"].jaxpr.eqns[-1]
    assert call.primitive.name == "pallas_call"
    assert len(call.params["grid_mapping"].grid) == 2
    assert call.params["grid_mapping"].grid == (8, 8)


# ---------------------------------------------------------------------------
# what the program no longer holds
# ---------------------------------------------------------------------------

def _eqns_outside_kernels(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _eqns_outside_kernels(sub)


@pytest.mark.parametrize("path", ["resident", "merged"])
def test_no_f32_product_outside_the_resident_kernel(monkeypatch, path):
    """delta's operands: on the merged path XLA widens dO and O to f32 and
    multiplies them ((b, s, h, d) f32: 64 MB a layer at the benchmark's
    shape); the resident path's program holds no f32 array of that size
    outside the kernel, and no reduction."""
    if path == "merged":
        monkeypatch.setattr(fap, "_RESIDENT_BWD_BUDGET", 0)
    b, s, h, d = 2, 256, 2, 64
    q = jnp.zeros((b, s, h, d), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda a, b_, c, ct: jax.vjp(
        lambda *x: fap.flash_attention_bshd_native(
            *x, causal=True, interpret=True), a, b_, c)[1](ct))(
                q, q, q, q).jaxpr
    eqns = list(_eqns_outside_kernels(jaxpr))
    wide = [e for e in eqns for o in e.outvars
            if o.aval.dtype == jnp.float32 and o.aval.size == b * s * h * d
            and e.primitive.name in ("mul", "convert_element_type")]
    sums = [e for e in eqns if e.primitive.name == "reduce_sum"
            and e.invars[0].aval.size == b * s * h * d]
    assert (len(wide), len(sums)) == ((0, 0) if path == "resident"
                                      else (3, 1))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [k.params["name"] for k in kernels] == ["flash_fwd", "flash_bwd"]


# ---------------------------------------------------------------------------
# flash.bwd_calls{path} and the benchmark's reader
# ---------------------------------------------------------------------------

def _bwd_calls():
    from paddle_tpu.observability import registry as reg
    ctr = reg.counter("flash.bwd_calls", ("path",))
    return {p: ctr.labels(path=p).value
            for p in ("resident", "merged", "split")}


@pytest.mark.parametrize("path", ["resident", "merged", "split"])
def test_bwd_calls_counts_one_a_traced_backward(monkeypatch, path):
    from paddle_tpu.observability import CATALOG
    assert CATALOG["flash.bwd_calls"]["type"] == "counter"
    assert CATALOG["flash.bwd_calls"]["labels"] == ("path",)
    if path != "resident":
        monkeypatch.setattr(fap, "_RESIDENT_BWD_BUDGET", 0)
    if path == "split":
        monkeypatch.setattr(fap, "_DQ_SCRATCH_BUDGET", 1)
    q = jnp.ones((1, 256, 2, 64), jnp.float32)

    def two_layers(q_):
        for _ in range(2):
            q_ = fap.flash_attention_bshd_native(q_, q_, q_, causal=True,
                                                 interpret=True)
        return jnp.sum(q_)

    before = _bwd_calls()
    jax.jit(two_layers)(q)                        # forward only: none
    assert _bwd_calls() == before
    grad = jax.jit(jax.grad(two_layers))
    grad(q)
    grad(q)                                       # traced once, run twice
    after = _bwd_calls()
    assert {p: after[p] - before[p] for p in after} == \
        {p: 2 * (p == path) for p in after}


def test_resident_share_reader_reads_the_counter_or_nothing():
    """The benchmark's reader: the share from a registry snapshot, None on
    a program without the counter (the parent) or outside a training run."""
    from benchmarks.lib import harness
    read = harness.layer_reader("flash_bwd_resident_pct.train")
    run = {"kind": "train"}
    assert read({}, None, run) is None
    assert read(None, None, run) is None

    def snap(**calls):
        return {"flash.bwd_calls": {"series": [
            {"labels": {"path": p}, "value": float(n)}
            for p, n in calls.items()]}}
    assert read(snap(resident=24), None, run) == 100.0
    assert read(snap(resident=18, merged=4, split=2), None, run) == 75.0
    assert read(snap(merged=24), None, run) == 0.0
    assert read(snap(resident=24), None, {"kind": "serve_open"}) is None


# ---------------------------------------------------------------------------
# flash.fwd_calls{operands} and the benchmark's reader (PR 32)
# ---------------------------------------------------------------------------

def _fwd_calls():
    from paddle_tpu.observability import registry as reg
    ctr = reg.counter("flash.fwd_calls", ("operands",))
    return {o: ctr.labels(operands=o).value for o in ("packed", "split")}


@pytest.mark.parametrize("operands", ["packed", "split"])
@pytest.mark.parametrize("transform", ["forward", "grad"])
def test_fwd_calls_counts_one_a_traced_forward(operands, transform):
    from paddle_tpu.observability import CATALOG
    assert CATALOG["flash.fwd_calls"]["type"] == "counter"
    assert CATALOG["flash.fwd_calls"]["labels"] == ("operands",)
    b, s, h, d = 1, 256, 2, 64

    def attend(x):
        if operands == "packed":
            return fap.flash_attention_packed_native(
                jnp.concatenate([x, x, x], -1), h, causal=True,
                interpret=True).reshape(b, s, h * d)
        x4 = x.reshape(b, s, h, d)
        return fap.flash_attention_bshd_native(
            x4, x4, x4, causal=True, interpret=True).reshape(b, s, h * d)

    def two_layers(x):
        return jnp.sum(attend(attend(x)))

    fn = jax.jit({"forward": two_layers,
                  "grad": jax.grad(two_layers)}[transform])
    x = jnp.ones((b, s, h * d), jnp.float32)
    before, bwd_before = _fwd_calls(), _bwd_calls()
    fn(x)
    fn(x)                                         # traced once, run twice
    after = _fwd_calls()
    assert {o: after[o] - before[o] for o in after} == \
        {o: 2 * (o == operands) for o in after}
    # the packed call's backward is counted where the split one's is
    assert _bwd_calls()["resident"] - bwd_before["resident"] == \
        (2 if transform == "grad" else 0)


def test_packed_share_reader_reads_the_counter_or_nothing():
    """The benchmark's reader: the share from a registry snapshot, None on
    a program without the counter (the parent) or outside a training run."""
    from benchmarks.lib import harness
    read = harness.layer_reader("flash_packed_pct.train")
    run = {"kind": "train"}
    assert read({}, None, run) is None
    assert read(None, None, run) is None

    def snap(**calls):
        return {"flash.fwd_calls": {"series": [
            {"labels": {"operands": o}, "value": float(n)}
            for o, n in calls.items()]}}
    assert read(snap(packed=24), None, run) == 100.0
    assert read(snap(packed=18, split=6), None, run) == 75.0
    assert read(snap(split=24), None, run) == 0.0
    assert read(snap(packed=24), None, {"kind": "serve_open"}) is None
