"""fp8 (e4m3) KV cache (ISSUE 20): the quantize grid generalized from
int8 to float8_e4m3fn behind the SAME codes+scales plumbing.

Covers:
* the parametrized ``quantize_kv`` keeps the int8 path byte-identical
  to PR 8's math while the fp8 path saturates (clip to ±448 before the
  cast — e4m3 overflows to NaN, not inf) and stays finite on extreme
  inputs;
* every-position fp8 logits parity against the unquantized engine for
  BOTH layer layouts × BOTH cache layouts, tolerance-tiered one band
  looser than int8 (e4m3 carries 3 mantissa bits vs int8's ~8);
* the kv-byte accounting stays honest: an fp8 row prices exactly like
  an int8 row (1-byte codes + f32 scale), the flight dump and autotune
  key carry the canonical dtype string, and the cache gate accepts the
  ``"fp8"`` shorthand while still rejecting garbage.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

# e4m3 has 3 mantissa bits (relative step ~1/16) vs int8's ~1/254 —
# one tolerance band looser than test_spec_quant's int8 tier (2e-2/5e-3)
FP8_RTOL, FP8_ATOL = 8e-2, 2e-2


def _tiny_model(seed=0):
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    return m


def _full_last_logits(model, ids):
    """Full-forward recompute of the next-token logits for a sequence.
    Right-padded to a multiple of 16: the model is causal, so the pad
    cannot reach the logits read, and the eager forward compiles once a
    bucket instead of once for every length the tests walk."""
    n = len(ids)
    width = min(-(-n // 16) * 16, model.config.max_position_embeddings)
    x = np.zeros((1, width), np.int32)
    x[0, :n] = np.asarray(ids, np.int32)
    return model(paddle.to_tensor(x)).numpy()[0, n - 1]


def _engine(model=None, **kw):
    from paddle_tpu.serving.engine import DecodeEngine
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 64)
    return DecodeEngine(model or _tiny_model(), **kw)


# ---------------------------------------------------------------------------
# grid units
# ---------------------------------------------------------------------------

def test_quantize_int8_default_byte_identical_to_pr8_math():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.cache import quantize_kv

    x = jax.random.normal(jax.random.key(0), (3, 5, 2, 16),
                          jnp.float32) * 3.0
    q, s = quantize_kv(x)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, jnp.asarray(1e-30, jnp.float32)) / 127.0
    ref = jnp.clip(jnp.round(xf / scale[..., None]),
                   -127.0, 127.0).astype(jnp.int8)
    assert q.dtype == jnp.int8
    assert np.array_equal(np.asarray(q), np.asarray(ref))
    assert np.array_equal(np.asarray(s), np.asarray(scale))


def test_fp8_quantize_saturates_and_bounds_error():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.cache import dequantize_kv, quantize_kv

    x = jax.random.normal(jax.random.key(1), (4, 7, 2, 16),
                          jnp.float32) * 5.0
    q, s = quantize_kv(x, jnp.float8_e4m3fn)
    assert q.dtype == jnp.dtype(jnp.float8_e4m3fn)
    back = np.asarray(dequantize_kv(q, s, jnp.float32))
    assert np.isfinite(back).all()
    # symmetric per-row grid: worst-case relative step of e4m3 is 2^-3
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    assert np.max(np.abs(back - np.asarray(x)) / amax) < 0.07
    # extreme magnitudes must clip onto the grid, never wrap to NaN
    big = jnp.asarray([[[[1e30, -1e30, 0.0, 5e29]]]], jnp.float32)
    qb, sb = quantize_kv(big, jnp.float8_e4m3fn)
    assert np.isfinite(np.asarray(dequantize_kv(qb, sb,
                                                jnp.float32))).all()


def test_kv_dtype_gate_accepts_fp8_rejects_garbage():
    import jax.numpy as jnp
    from paddle_tpu.serving.cache import _as_kv_dtypes

    assert _as_kv_dtypes(None) == (None, None)
    for spec in ("fp8", "float8_e4m3fn", jnp.float8_e4m3fn):
        code, scale = _as_kv_dtypes(spec)
        assert code == jnp.dtype(jnp.float8_e4m3fn)
        assert scale == jnp.float32
    with pytest.raises(ValueError):
        _as_kv_dtypes("float16")


def test_fp8_autotune_key_carries_dtype_value():
    import jax.numpy as jnp
    from paddle_tpu.kernels import decode_attention as dat

    k8 = dat.autotune_key(2, 64, 2, 16, 1, jnp.float32, kv_dtype="int8")
    kf = dat.autotune_key(2, 64, 2, 16, 1, jnp.float32,
                          kv_dtype=jnp.float8_e4m3fn)
    assert k8["kv_dtype"] == "int8"
    assert kf["kv_dtype"] == "float8_e4m3fn"
    assert k8 != kf          # the grids can never collide in the cache
    # both select the quantized variant set (shared kernel structure)
    v8 = {c["variant"] for c in dat._candidates(k8)}
    vf = {c["variant"] for c in dat._candidates(kf)}
    assert v8 == vf and "masked_q8" in vf


# ---------------------------------------------------------------------------
# fp8 logits parity — every position, both layer/cache layouts
# ---------------------------------------------------------------------------

@pytest.mark.slow   # per-position full-forward recomputes; the CI
@pytest.mark.parametrize("paged", [False, True])
def test_fp8_engine_logits_parity_every_position(paged):
    # serving job runs this file UNFILTERED (like the int8 twin suite)
    m = _tiny_model()
    kw = {"kv_dtype": "fp8"}
    if paged:
        kw["page_size"] = 16
    eng = _engine(m, **kw)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 512, (5,)), rng.integers(0, 512, (17,))]
    seqs = []
    for i, p in enumerate(prompts):
        tok, logits = eng.prefill(i, p, temperature=0.0)
        np.testing.assert_allclose(np.asarray(logits),
                                   _full_last_logits(m, p),
                                   rtol=FP8_RTOL, atol=FP8_ATOL)
        seqs.append(list(p) + [tok])
    for _ in range(6):
        toks = [s[-1] for s in seqs]
        nt, logits = eng.decode(toks, [True, True], [0.0, 0.0], [0, 0],
                                [1.0, 1.0])
        for b in range(2):
            np.testing.assert_allclose(
                np.asarray(logits[b]), _full_last_logits(m, seqs[b]),
                rtol=FP8_RTOL, atol=FP8_ATOL)
            seqs[b].append(int(nt[b]))
    assert eng.decode_compile_count == 1
    assert eng.prefill_compile_count == 1


def test_fp8_paged_greedy_decode_runs_fast():
    """Tier-1's fast fp8 smoke: the paged fp8 engine completes a short
    greedy drive compile-once (the every-position sweeps above are
    slow-marked)."""
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    m = _tiny_model()
    eng = _engine(m, kv_dtype="fp8", page_size=16)
    sched = ContinuousBatchingScheduler(eng)
    rng = np.random.default_rng(3)
    rids = [sched.submit(Request(prompt=rng.integers(0, 512, (n,)),
                                 max_new_tokens=8))
            for n in (5, 11)]
    res = sched.run()
    assert all(len(res[r].tokens) == 8 for r in rids)
    assert eng.decode_compile_count == 1


# ---------------------------------------------------------------------------
# byte accounting stays honest
# ---------------------------------------------------------------------------

def test_fp8_row_bytes_match_int8_and_flight_dtype():
    m = _tiny_model()
    eng_bf = _engine(m)
    eng_i8 = _engine(m, kv_dtype="int8")
    eng_f8 = _engine(m, kv_dtype="fp8")
    # 1-byte codes + 4-byte scale per (row, head): identical to int8
    assert eng_f8.kv_row_bytes() == eng_i8.kv_row_bytes()
    assert eng_f8.kv_row_bytes() < eng_bf.kv_row_bytes()
    hd = eng_f8._head_dim
    per_head = hd * 1 + 4
    assert eng_f8.kv_row_bytes() == (eng_f8._layers * eng_f8._heads
                                     * per_head * 2)
    assert eng_f8.kv_pool_bytes() == (eng_f8.num_slots * eng_f8.max_len
                                      * eng_f8.kv_row_bytes())
    # canonical dtype string everywhere downstream of the gate
    assert eng_f8._kv_dtype_arg() == "float8_e4m3fn"
    assert eng_f8.flight_state()["kv_dtype"] == "float8_e4m3fn"
    assert eng_f8.cache.k.dtype == eng_f8.kv_dtype
