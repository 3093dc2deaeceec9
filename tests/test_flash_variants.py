"""Interpret-mode parity tests for the round-6 flash-attention variants
(bf16chain / iotafree / parq / pipelined — flash_attention_pallas.py) vs
the O(S^2) XLA reference, forward AND backward, causal and non-causal,
including odd-tail shapes and the streamed / split-backward paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.kernels.flash_attention_pallas as fap
from paddle_tpu.kernels.flash_attention_pallas import (
    _reference_bhsd, flash_attention_bhsd)

#: every selectable forward variant (bwd strips parq/pipelined)
VARIANTS = ["iotafree", "bf16chain", "bf16chain+iotafree", "parq",
            "pipelined", "iotafree+pipelined"]
#: (b, h, s, d) — 384 is the odd-tail shape (not a multiple of the 512
#: default block: _prep_blocks shrinks to 128), 128-d hits the wide-head
#: lane layout
SHAPES = [(1, 2, 256, 64), (1, 2, 384, 64), (2, 1, 256, 128)]


def _tol(variant):
    # bf16chain truncates the softmax chain to bf16 (~2^-8 relative on p)
    if "bf16chain" in variant:
        return dict(atol=3e-2, rtol=3e-2)
    return dict(atol=1e-5, rtol=1e-5)


def _qkv(shape, seed=0):
    b, h, s, d = shape
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, h, s, d), jnp.float32),
            jnp.asarray(rng.randn(b, h, s, d), jnp.float32),
            jnp.asarray(rng.randn(b, h, s, d), jnp.float32))


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", SHAPES)
def test_variant_forward_matches_reference(variant, causal, shape):
    q, k, v = _qkv(shape)
    d = shape[-1]
    out = flash_attention_bhsd(q, k, v, causal=causal, interpret=True,
                               variant=variant)
    ref = _reference_bhsd(q, k, v, causal, 1.0 / d ** 0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol(variant))


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("causal", [False, True])
def test_variant_backward_matches_reference(variant, causal):
    b, h, s, d = 1, 2, 256, 64
    q, k, v = _qkv((b, h, s, d), seed=1)

    def f(q_, k_, v_):
        return jnp.sum(jnp.sin(flash_attention_bhsd(
            q_, k_, v_, causal=causal, interpret=True, variant=variant)))

    def r(q_, k_, v_):
        return jnp.sum(jnp.sin(_reference_bhsd(q_, k_, v_, causal,
                                               1.0 / d ** 0.5)))

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    if "bf16chain" in variant:
        tol = dict(atol=5e-2, rtol=5e-2)
    else:
        tol = dict(atol=2e-4, rtol=1e-3)
    for name, a, b_ in zip("dq dk dv".split(), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   err_msg="%s/%s" % (variant, name),
                                   **tol)


@pytest.mark.parametrize("variant", ["iotafree", "bf16chain"])
def test_variant_streamed_long_seq_path(variant):
    """Variants must also hold on the grid-streamed forward (taken when
    K/V exceed the resident VMEM budget)."""
    b, h, s, d = 1, 2, 512, 64
    q, k, v = _qkv((b, h, s, d), seed=5)
    old = fap._RESIDENT_KV_BUDGET
    fap._RESIDENT_KV_BUDGET = 1
    try:
        out = flash_attention_bhsd(q, k, v, causal=True, interpret=True,
                                   variant=variant)
    finally:
        fap._RESIDENT_KV_BUDGET = old
    ref = _reference_bhsd(q, k, v, True, 1.0 / d ** 0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol(variant))


def test_pipelined_ignores_resident_budget():
    """The pipelined forward streams K/V chunks itself (O(block_k) VMEM)
    — it must produce reference numerics regardless of the resident
    budget the other paths dispatch on."""
    b, h, s, d = 1, 2, 512, 64
    q, k, v = _qkv((b, h, s, d), seed=6)
    ref = _reference_bhsd(q, k, v, True, 1.0 / d ** 0.5)
    old = fap._RESIDENT_KV_BUDGET
    for budget in (1, old):
        fap._RESIDENT_KV_BUDGET = budget
        try:
            out = flash_attention_bhsd(q, k, v, causal=True,
                                       interpret=True,
                                       variant="pipelined")
        finally:
            fap._RESIDENT_KV_BUDGET = old
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
@pytest.mark.parametrize("variant", ["iotafree", "bf16chain+iotafree"])
def test_variant_split_backward_parity(variant):
    """Variant kernels on the SPLIT two-kernel backward (forced via a tiny
    dq-scratch budget) must match the variant's merged-backward grads."""
    b, s, h, d = 1, 1024, 2, 64
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
                interpret=True, variant=variant)
        finally:
            fap._DQ_SCRATCH_BUDGET, fap._RESIDENT_BWD_BUDGET = old
        return jnp.sum(out * ct)

    g_merged = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, 4 * 1024 * 1024)
    g_split = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, 64 * 1024)
    for gm, gs, name in zip(g_merged, g_split, "qkv"):
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gm),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("variant", ["iotafree", "parq"])
def test_variant_with_lse_grads(variant):
    """flash_attention_bshd_with_lse under a variant: the (out, lse) pair
    and the lse-cotangent backward stay reference-exact."""
    from paddle_tpu.kernels.flash_attention_pallas import \
        flash_attention_bshd_with_lse

    b, s, h, d = 1, 256, 2, 64
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def loss_flash(q_, k_, v_):
        out, lse = flash_attention_bshd_with_lse(
            q_, k_, v_, causal=True, interpret=True, variant=variant)
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


def test_iotafree_band_mask_bit_exact():
    """iotafree is a pure mask-arithmetic rewrite — its output must be
    BIT-identical to base (same where/select semantics), not just close."""
    q, k, v = _qkv((1, 2, 256, 64), seed=7)
    base = flash_attention_bhsd(q, k, v, causal=True, interpret=True,
                                variant="base")
    iof = flash_attention_bhsd(q, k, v, causal=True, interpret=True,
                               variant="iotafree")
    np.testing.assert_array_equal(np.asarray(base), np.asarray(iof))


def test_cross_attention_kv_longer(variantless=True):
    """sk != s (cross attention, non-causal) through the variant plumbing."""
    b, h, s, sk, d = 1, 2, 128, 256, 64
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, sk, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, sk, d), jnp.float32)
    ref = _reference_bhsd(q, k, v, False, 1.0 / d ** 0.5)
    for variant in ("base", "iotafree", "pipelined"):
        out = flash_attention_bhsd(q, k, v, causal=False, interpret=True,
                                   variant=variant)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=variant)


# ---------------------------------------------------------------------------
# the causal band: static sub-tiles (both candidate tile edges)
# ---------------------------------------------------------------------------

TILES = [128, 256]


def _bshd(shape, seed, scale=0.5):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(*shape), jnp.float32) * scale
                 for _ in range(4))


def _ref_bshd(q, k, v):
    return jnp.swapaxes(_reference_bhsd(
        *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), True,
        1.0 / q.shape[-1] ** 0.5), 1, 2)


def _band_parity(monkeypatch, tile, d, block_q, block_k, variant=None,
                 resident=True, merged=True, s=1024):
    """Forward and gradients of one causal call whose diagonal blocks are
    walked in ``tile``-edge sub-tiles, against the O(S^2) reference.  The
    backward is the merged kernel, or the split pair: the resident one
    (which these shapes would take) has tests/test_flash_resident_bwd.py."""
    monkeypatch.setattr(fap, "_BAND_TILE", tile)
    monkeypatch.setattr(fap, "_RESIDENT_BWD_BUDGET", 0)
    if not resident:
        monkeypatch.setattr(fap, "_RESIDENT_KV_BUDGET", 1)
    if not merged:
        monkeypatch.setattr(fap, "_DQ_SCRATCH_BUDGET", 1)
    assert fap._band_tile(block_k) == tile
    q, k, v, ct = _bshd((1, s, 2, d), seed=tile + d)

    def f(q_, k_, v_):
        return jnp.sum(ct * fap.flash_attention_bshd_native(
            q_, k_, v_, causal=True, block_q=block_q, block_k=block_k,
            interpret=True, variant=variant))

    def r(q_, k_, v_):
        return jnp.sum(ct * _ref_bshd(q_, k_, v_))

    out = fap.flash_attention_bshd_native(
        q, k, v, causal=True, block_q=block_q, block_k=block_k,
        interpret=True, variant=variant)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref_bshd(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("dq dk dv".split(), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("tile", TILES)
def test_band_resident_forward_merged_backward(monkeypatch, tile, d):
    """The benchmark cell's kernels: (s, block) = (1,024, 512)."""
    _band_parity(monkeypatch, tile, d, 512, 512)


@pytest.mark.parametrize("tile", TILES)
def test_band_streamed_forward_split_backward(monkeypatch, tile):
    """The long-sequence family: grid-streamed forward, dq and dkv kernels."""
    _band_parity(monkeypatch, tile, 64, 512, 512, resident=False,
                 merged=False)


@pytest.mark.parametrize("family", ["resident", "streamed", "pipelined"])
@pytest.mark.parametrize("tile,block_k", [(128, 256), (128, 128),
                                          (256, 256)])
def test_band_of_several_k_blocks(monkeypatch, tile, block_k, family):
    """block_k < block_q: the band is block_q // block_k cells, each at
    its own static offset from the diagonal (merged and split backward)."""
    _band_parity(monkeypatch, tile, 64, 512, block_k, s=512,
                 variant="pipelined" if family == "pipelined" else None,
                 resident=family != "streamed",
                 merged=family != "streamed")


@pytest.mark.parametrize("tile", TILES)
def test_band_with_lse_cotangent(monkeypatch, tile):
    """flash_attention_bshd_with_lse over a sub-tiled band, the loss
    consuming the lse too (the ring-attention inner's shape)."""
    from paddle_tpu.kernels.flash_attention_pallas import \
        flash_attention_bshd_with_lse
    monkeypatch.setattr(fap, "_BAND_TILE", tile)
    s, d = 512, 64
    q, k, v, _ = _bshd((1, s, 2, d), seed=tile)
    scale = 1.0 / np.sqrt(d)

    def loss_flash(q_, k_, v_):
        out, lse = flash_attention_bshd_with_lse(
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
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("block_q,block_k,t,off", [
    (8, 8, 2, 0), (8, 8, 4, 0), (8, 8, 8, 0), (8, 4, 2, 0), (8, 4, 2, -4),
    (8, 2, 2, -2), (8, 2, 2, -6), (12, 4, 4, -8), (16, 8, 4, -8),
    (4, 8, 4, 4)])
def test_live_tiles_are_the_tiles_with_a_visible_element(block_q, block_k,
                                                         t, off):
    """Brute force over small grids: a tile is live iff the mask leaves
    any of its elements visible, masked iff it hides any; the merged runs
    cover exactly the live tiles."""
    i = np.arange(block_q)[:, None]
    j = np.arange(block_k)[None, :]
    vis = j <= i + off
    want = []
    for r in range(block_q // t):
        for c in range(block_k // t):
            tile = vis[r * t:(r + 1) * t, c * t:(c + 1) * t]
            if tile.any():
                want.append((r, c, not tile.all()))
                if not tile.all():      # the one constant triangle
                    np.testing.assert_array_equal(
                        tile, np.tril(np.ones((t, t), bool)))
    assert fap._live_tiles(block_q, block_k, t, off) == want
    covered = np.zeros_like(vis)
    for rows, cols, masked in fap._band_runs(block_q, block_k, t, off):
        assert not covered[rows, cols].any()
        covered[rows, cols] = True
        assert masked == (not vis[rows, cols].all())
    tiles = np.zeros_like(vis)
    for r, c, _ in want:
        tiles[r * t:(r + 1) * t, c * t:(c + 1) * t] = True
    np.testing.assert_array_equal(covered, tiles)


def test_one_tile_band_is_one_masked_step():
    """block_q == block_k == t: the walk is the whole block under the
    mask, as before the band was sub-tiled."""
    assert fap._band_runs(128, 128, 128, 0) == [
        (slice(0, 128), slice(0, 128), True)]
    assert fap._band_tile(128) == 128
    # a block no candidate divides stays whole
    assert fap._band_tile(192) == 192


#: sha256 over the kernel bodies (the jaxprs inside the pallas_calls) of
#: the non-causal programs at the parent of PR 26 (commit 6666a21), unused
#: scalar equations dropped: the sub-tiled band must leave non-causal
#: calls exactly as they were.  To regenerate after a deliberate change:
#: print _noncausal_kernels_hash(...) on both trees.
_NONCAUSAL_KERNELS = {
    "resident+merged":
        "5b2d29c08628afb2c84b1c4c61afc5e5e8b47760d2271d26f5ab23c4b68acef8",
    "streamed+split":
        "2b873f44dab64228bc77267bd05a4d92d3c9e6c17f54cc894c3786ca02192590",
}


def _kernel_bodies(jaxpr):
    """The kernel jaxprs of every pallas_call under ``jaxpr``, in order."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(str(eqn.params["jaxpr"]))
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                out += _kernel_bodies(sub)
    return out


def _noncausal_kernels_hash(monkeypatch, family):
    import hashlib
    import re
    # the merged and split kernels: the resident backward, which this
    # shape would take, did not exist at that commit
    monkeypatch.setattr(fap, "_RESIDENT_BWD_BUDGET", 0)
    if family == "streamed+split":
        monkeypatch.setattr(fap, "_RESIDENT_KV_BUDGET", 1)
        monkeypatch.setattr(fap, "_DQ_SCRATCH_BUDGET", 1)
    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    bodies = _kernel_bodies(jax.make_jaxpr(jax.grad(
        lambda a, b, c: jnp.sum(fap.flash_attention_bshd_native(
            a, b, c, causal=False, block_q=128, block_k=128,
            interpret=True)), argnums=(0, 1, 2)))(q, q, q).jaxpr)
    assert len(bodies) == (2 if family == "resident+merged" else 3)
    text = re.sub(r"^\s*_:\S+ = .*\n", "", "\n".join(bodies), flags=re.M)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("family", sorted(_NONCAUSAL_KERNELS))
def test_noncausal_kernel_bodies_are_the_parents(monkeypatch, family):
    assert _noncausal_kernels_hash(monkeypatch, family) == \
        _NONCAUSAL_KERNELS[family]


# ---------------------------------------------------------------------------
# packed operands (PR 32): q, k and v read where the fused projection wrote
# them, through the block index maps of every forward and backward kernel
# ---------------------------------------------------------------------------

# (s, h, d, block_q, block_k, variant, patched budgets, the forward's
# family, the backward's path, (hg_f, hg_b))
PACKED = [
    pytest.param(1024, 4, 64, 512, 512, None, {}, "resident", "resident",
                 (4, 2), id="d64-several-blocks-hg_f4-hg_b2"),
    pytest.param(512, 2, 128, 512, 512, None, {}, "resident", "resident",
                 (2, 1), id="d128-one-block-hg_f2-hg_b1"),
    pytest.param(256, 2, 64, 512, 512, None, {}, "resident", "resident",
                 (2, 2), id="d64-one-block"),
    # 64 block pairs: too long a walk for the resident backward
    pytest.param(1024, 2, 64, 128, 128, None, {}, "resident", "merged",
                 (2, 2), id="d64-falls-to-merged"),
    pytest.param(512, 2, 64, 128, 128, None,
                 {"_RESIDENT_BWD_BUDGET": 0, "_RESIDENT_KV_BUDGET": 1,
                  "_DQ_SCRATCH_BUDGET": 1}, "streamed", "split", (2, 2),
                 id="d64-streamed-split"),
    pytest.param(512, 4, 64, 256, 128, "pipelined", {}, "pipelined",
                 "resident", (4, 2), id="d64-pipelined"),
    pytest.param(512, 2, 128, 256, 256, "parq", {}, "resident", "resident",
                 (2, 1), id="d128-parq"),
]


@pytest.mark.parametrize("s,h,d,bq,bk,variant,budgets,family,path,groups",
                         PACKED)
def test_packed_operands_equal_the_three_slices_bit_for_bit(
        monkeypatch, s, h, d, bq, bk, variant, budgets, family, path,
        groups):
    """``flash_attention_packed_native`` of a (b, s, 3*h*d) buffer against
    ``flash_attention_bshd_native`` of its three column slices: the same
    kernels at the same specs over the same bytes, so the output and the
    gradient are equal bit for bit, in the forward's families and down the
    backward's rungs, the two directions at head groups of their own."""
    for name, value in budgets.items():
        monkeypatch.setattr(fap, name, value)
    b, hd = 2, h * d
    fwd_spec, bwd_spec = fap._native_specs(b, s, s, h, d, jnp.float32, True,
                                           bq, bk, variant)
    assert bwd_spec[0] == path
    assert (fwd_spec[3], bwd_spec[3 if path == "split" else 4]) == groups
    assert family == ("pipelined" if variant == "pipelined" else
                      "resident" if fap._kv_fits_resident(s, groups[0] * d)
                      else "streamed")
    rng = np.random.RandomState(s + d)
    qkv = jnp.asarray(rng.randn(b, s, 3 * hd), jnp.float32) * 0.5
    ct = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    kw = dict(causal=True, block_q=bq, block_k=bk, interpret=True,
              variant=variant)

    def packed(x):
        return fap.flash_attention_packed_native(x, h, **kw)

    def sliced(x):
        return fap.flash_attention_bshd_native(
            *(x[:, :, i * hd:(i + 1) * hd].reshape(b, s, h, d)
              for i in range(3)), **kw)

    got, got_vjp = jax.vjp(packed, qkv)
    want, want_vjp = jax.vjp(sliced, qkv)
    assert bool(jnp.all(got == want))
    (dgot,), (dwant,) = got_vjp(ct), want_vjp(ct)
    assert dgot.shape == qkv.shape
    for i, name in enumerate(("dq", "dk", "dv")):
        part = slice(i * hd, (i + 1) * hd)
        assert float(jnp.max(jnp.abs(dwant[:, :, part]))) > 0, name
        assert bool(jnp.all(dgot[:, :, part] == dwant[:, :, part])), name
    # and the packed call is the reference's attention, not only the twin
    q, k, v = (qkv[:, :, i * hd:(i + 1) * hd].reshape(b, s, h, d)
               for i in range(3))
    np.testing.assert_allclose(np.asarray(got), np.asarray(_ref_bshd(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_packed_entry_refuses_a_width_that_is_not_three_parts():
    with pytest.raises(ValueError, match="3 x 2 heads"):
        fap.flash_attention_packed_native(
            jnp.zeros((1, 128, 200), jnp.float32), 2, interpret=True)


def test_packed_cotangent_is_a_sum_of_pads_not_a_concatenate():
    """What XLA is handed for the fused buffer's cotangent: three pads
    and two adds (the transpose of three slices, which it fuses into the
    projection's gradient GEMMs); a concatenate becomes three
    dynamic-update-slice passes over a (b, s, 3*h*d) buffer
    (tests/test_flash_tpu_compile.py holds the compiled program to it)."""
    qkv = jnp.zeros((1, 256, 3 * 128), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x, ct: jax.vjp(
        lambda y: fap.flash_attention_packed_native(
            y, 2, causal=True, interpret=True), x)[1](ct))(
                qkv, jnp.zeros((1, 256, 2, 64), jnp.float32)).jaxpr
    names = [e.primitive.name for e in jaxpr.eqns]
    assert names.count("pad") == 3
    assert "concatenate" not in names and "dynamic_update_slice" not in names


# ---------------------------------------------------------------------------
# flash.score_elements: how far the band walk engages
# ---------------------------------------------------------------------------

def _score_counter():
    from paddle_tpu.observability import registry as reg
    ctr = reg.counter("flash.score_elements", ("which",))
    return {w: ctr.labels(which=w).value for w in ("computed", "causal")}


@pytest.mark.parametrize("tile,computed,dead_pct", [
    (512, 786432, 33.3), (128, 589824, 11.0), (256, 655360, 19.9)])
def test_score_elements_at_the_benchmark_shape(tile, computed, dead_pct):
    """s = 1,024 in 512-blocks: the whole-block walk, t = 128 and t = 256
    (arithmetic from shapes)."""
    got, causal = fap.score_elements(1024, 512, tile)
    assert (got, causal) == (computed, 1024 * 1025 // 2)
    assert round(100.0 * (got - causal) / got, 1) == dead_pct


@pytest.mark.parametrize("transform", ["forward", "grad"])
def test_score_elements_counted_once_a_traced_causal_call(monkeypatch,
                                                          transform):
    from paddle_tpu.observability import CATALOG
    assert CATALOG["flash.score_elements"]["type"] == "counter"
    assert CATALOG["flash.score_elements"]["labels"] == ("which",)
    monkeypatch.setattr(fap, "_BAND_TILE", 128)
    b, s, h, d = 2, 256, 2, 64
    q = jnp.ones((b, s, h, d), jnp.float32)

    def f(q_, causal):
        return jnp.sum(fap.flash_attention_bshd_native(
            q_, q_, q_, causal=causal, interpret=True))

    fn = {"forward": f, "grad": jax.grad(f)}[transform]
    before = _score_counter()
    jax.jit(fn, static_argnums=1)(q, False)       # non-causal: not counted
    assert _score_counter() == before
    jitted = jax.jit(fn, static_argnums=1)
    jitted(q, True)
    jitted(q, True)                               # traced once, run twice
    after = _score_counter()
    # one 256-block a head: 3 of its 4 sub-tiles are live
    assert after["computed"] - before["computed"] == b * h * 3 * 128 * 128
    assert after["causal"] - before["causal"] == b * h * s * (s + 1) // 2


def test_dead_score_reader_reads_the_counter_or_nothing():
    """The benchmark's reader: the share from a registry snapshot, None on
    a program without the counter (the parent) or outside a training run."""
    from benchmarks.lib import harness
    read = harness.layer_reader("flash_dead_score_pct.train")
    run = {"kind": "train"}
    assert read({}, None, run) is None
    assert read(None, None, run) is None
    snap = {"flash.score_elements": {"series": [
        {"labels": {"which": "computed"}, "value": 24 * 256 * 589824.0},
        {"labels": {"which": "causal"}, "value": 24 * 256 * 524800.0}]}}
    assert round(read(snap, None, run), 1) == 11.0
    assert read(snap, None, {"kind": "serve_open"}) is None
