"""Speculative + int8-quantized decode (ISSUE 8): the two multiplicative
levers on the decode KV bandwidth wall, as composable engine modes.

Covers the acceptance criteria:
* speculative GREEDY decode is BIT-identical to non-speculative decode
  on the paged engine — across slot churn, prefix-cache hits, and
  recompute preemption (the accept rule compares exact argmaxes, so any
  divergence is a real bug, not tolerance);
* int8 KV logits match the unquantized engine within quantization
  tolerance at EVERY position, both cache layouts (paged and the slotted A/B), and
  the model-level ``gen_paged_cache(kv_dtype="int8")`` path;
* seed reproducibility with spec on: ``generate(seed=s)`` on the
  engine_for-cached engine is bit-stable (ONE threaded key per verify
  iteration regardless of accepted count);
* compile-once across accept-rate extremes: all-accept AND all-reject
  verify steps run through the same single program (fixed draft length
  k => exactly two static decode-side programs: verify + the
  single-token fallback);
* unit behavior: symmetric int8 quantization round-trip bound,
  ``spec_accept`` accept/emit/rollback semantics, prompt-lookup
  proposals, the spec_proposed/spec_accepted counter pair, and the
  opt-in kv_quant_error gauge.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM


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
    kw.setdefault("page_size", 16)
    return DecodeEngine(model or _tiny_model(), **kw)


# ---------------------------------------------------------------------------
# int8 quantization units
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bound():
    import jax.numpy as jnp
    from paddle_tpu.serving.cache import dequantize_kv, quantize_kv
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 3, 8, 16)) * 5, jnp.float32)
    q, s = quantize_kv(x)
    assert str(q.dtype) == "int8" and str(s.dtype) == "float32"
    assert s.shape == (4, 3, 8)
    back = dequantize_kv(q, s, jnp.float32)
    # symmetric amax/127 grid: |err| <= scale/2 per element (+ rounding)
    amax = np.abs(np.asarray(x)).max(axis=-1, keepdims=True)
    bound = amax / 127.0 * 0.5 + 1e-6
    assert (np.abs(np.asarray(back) - np.asarray(x)) <= bound).all()
    # the per-row amax itself is exactly representable => row max
    # round-trips to within one grid step everywhere
    assert np.abs(np.asarray(back)).max() <= np.abs(np.asarray(x)).max() \
        * (1 + 1e-6)


def test_kv_dtype_validation_and_row_bytes():
    import jax.numpy as jnp
    m = _tiny_model()
    with pytest.raises(ValueError):
        _engine(m, kv_dtype="float16")
    eng8 = _engine(m, kv_dtype="int8")
    eng = _engine(m)
    d = 16     # tiny head_dim
    # int8 row = codes + one f32 scale per head; unquantized = f32 rows
    assert eng8.kv_row_bytes() / eng.kv_row_bytes() == \
        pytest.approx((d + 4) / (4 * d))
    assert str(eng8.cache.k.dtype) == "int8"
    assert eng8.cache.k_scale.shape == eng8.cache.k.shape[:-1]
    assert jnp.issubdtype(eng8.cache.k_scale.dtype, jnp.floating)


# ---------------------------------------------------------------------------
# int8 logits parity — every position, both layer/cache layouts
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_int8_paged_engine_logits_parity_every_position():
    # slow: per-position full-forward recomputes (the CI serving job
    # runs this file UNFILTERED, so the every-position contract is
    # enforced there; tier-1 keeps the fast int8 parity tests below)
    m = _tiny_model()
    eng = _engine(m, kv_dtype="int8")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, (5,)), rng.integers(0, 512, (19,))]
    seqs = []
    for i, p in enumerate(prompts):
        tok, logits = eng.prefill(i, p, temperature=0.0)
        np.testing.assert_allclose(np.asarray(logits),
                                   _full_last_logits(m, p),
                                   rtol=2e-2, atol=5e-3)
        seqs.append(list(p) + [tok])
    for _ in range(6):
        toks = [s[-1] for s in seqs]
        nt, logits = eng.decode(toks, [True, True], [0.0, 0.0], [0, 0],
                                [1.0, 1.0])
        for b in range(2):
            np.testing.assert_allclose(
                np.asarray(logits[b]), _full_last_logits(m, seqs[b]),
                rtol=2e-2, atol=5e-3)
            seqs[b].append(int(nt[b]))
    assert eng.decode_compile_count == 1
    assert eng.prefill_compile_count == 1


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_int8_slotted_engine_logits_parity():
    """The slotted A/B layout gains kv_dtype=int8 too (bucketed prefill
    writes quantize; decode reads dequantize through masked_q8)."""
    m = _tiny_model()
    eng = _engine(m, paged=False, kv_dtype="int8")
    rng = np.random.default_rng(2)
    p = rng.integers(0, 512, (9,))
    tok, logits = eng.prefill(0, p, temperature=0.0)
    np.testing.assert_allclose(np.asarray(logits), _full_last_logits(m, p),
                               rtol=2e-2, atol=5e-3)
    seq = list(p) + [tok]
    for _ in range(4):
        nt, logits = eng.decode([seq[-1], 0], [True, False], [0.0, 0.0],
                                [0, 0], [1.0, 1.0])
        np.testing.assert_allclose(
            np.asarray(logits[0]), _full_last_logits(m, seq),
            rtol=2e-2, atol=5e-3)
        seq.append(int(nt[0]))
    assert eng.decode_compile_count == 1


@pytest.mark.slow
def test_int8_model_level_paged_cache_parity():
    """model(x, cache=gen_paged_cache(kv_dtype='int8')) decodes through
    the q8 gather path with no engine in the loop.  (slow: enforced in
    the unfiltered CI serving job.)"""
    m = _tiny_model()
    ids = np.random.default_rng(3).integers(0, 512, (1, 8)).astype("int32")
    full = m(paddle.to_tensor(ids)).numpy()
    cache = m.gen_paged_cache(1, max_len=64, page_size=16, kv_dtype="int8")
    assert str(cache.k.dtype) == "int8" and cache.quantized
    outs = []
    for t in range(8):
        logit, cache = m(paddle.to_tensor(ids[:, t:t + 1]), cache=cache)
        outs.append(logit.numpy())
    np.testing.assert_allclose(np.concatenate(outs, axis=1), full,
                               rtol=2e-2, atol=5e-3)
    assert int(np.asarray(cache.lengths)[0]) == 8


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_int8_prefix_sharing_and_cow_preserve_scales():
    """CoW copies the scale pages with the code pages: two sharers of a
    quantized tail page decode independently with correct dequant."""
    m = _tiny_model()
    eng = _engine(m, num_slots=2, max_len=64, page_size=8,
                  kv_dtype="int8", seed=5)
    prompt = np.random.default_rng(17).integers(0, 512, (12,))
    tok0, _ = eng.prefill(0, prompt, temperature=0.0)
    tok1, _ = eng.prefill(1, prompt, temperature=0.0)   # hits + CoWs
    assert tok1 == tok0
    # both decode greedily; a fresh never-shared engine must agree
    def stream(e, slot, first, n):
        toks = [int(first)]
        for _ in range(n):
            feed = [0, 0]
            feed[slot] = toks[-1]
            act = [False, False]
            act[slot] = True
            nt, _ = e.decode(feed, act, [0.0, 0.0], [0, 0], [1.0, 1.0])
            toks.append(int(nt[slot]))
        return toks
    s0 = stream(eng, 0, tok0, 6)
    s1 = stream(eng, 1, tok1, 6)
    ref = _engine(m, num_slots=2, max_len=64, page_size=8,
                  kv_dtype="int8", seed=5)
    rtok, _ = ref.prefill(0, prompt, temperature=0.0)
    r0 = stream(ref, 0, rtok, 6)
    assert s0 == r0 and s1 == r0, \
        "int8 CoW/sharing perturbed a sharer's stream"


# ---------------------------------------------------------------------------
# speculative decode — greedy bit-parity
# ---------------------------------------------------------------------------

def _run_sched(m, prompts, spec_k, kv_dtype=None, temperature=0.0,
               max_new=10, num_slots=2, num_pages=None, seed=7,
               eos=None, max_len=64, page_size=16, overlap=None):
    from paddle_tpu.serving.engine import DecodeEngine
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    eng = DecodeEngine(m, num_slots=num_slots, max_len=max_len,
                       page_size=page_size, spec_k=spec_k,
                       kv_dtype=kv_dtype, num_pages=num_pages, seed=seed)
    sched = ContinuousBatchingScheduler(eng, overlap=overlap)
    rids = [sched.submit(Request(prompt=p, max_new_tokens=max_new,
                                 temperature=temperature,
                                 eos_token_id=eos))
            for p in prompts]
    res = sched.run()
    return [res[r] for r in rids], eng


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_spec_greedy_bit_identical_across_churn_and_prefix_hits():
    """The acceptance criterion: greedy output through the speculative
    verify program equals non-speculative decode EXACTLY — with more
    requests than slots (churn) and repeated prompts (prefix hits)."""
    m = _tiny_model()
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 512, (16,))
    prompts = [shared if i % 2 else rng.integers(0, 512, (5 + 3 * i,))
               for i in range(5)]
    base, _ = _run_sched(m, prompts, spec_k=0)
    for k in (1, 4):
        spec, eng = _run_sched(m, prompts, spec_k=k)
        assert [list(r.tokens) for r in spec] == \
            [list(r.tokens) for r in base], \
            "spec_k=%d greedy diverged from non-speculative" % k
        assert eng.verify_compile_count == 1
        assert eng.prefill_compile_count == 1
        # the single-token fallback stayed compiled-or-untouched
        assert eng.decode_compile_count <= 1


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_spec_greedy_bit_identical_through_preemption_resume():
    """A tight pool forces recompute preemption mid-run; the resumed
    requests' greedy completions still match the uncontended
    non-speculative run bit-for-bit."""
    from paddle_tpu import observability as obs
    m = _tiny_model()
    rng = np.random.default_rng(71)
    prompts = [rng.integers(0, 512, (24,)) for _ in range(2)]
    base, _ = _run_sched(m, prompts, spec_k=0, max_new=8, max_len=48,
                         num_pages=12, page_size=8)
    before = obs.counter("serving.preemptions").value
    tight, eng = _run_sched(m, prompts, spec_k=3, max_new=8, max_len=48,
                            num_pages=6, page_size=8)
    assert obs.counter("serving.preemptions").value > before, \
        "pool was not tight enough to exercise preemption under spec"
    for t, b in zip(tight, base):
        assert t.finish_reason == b.finish_reason == "length"
        np.testing.assert_array_equal(t.tokens, b.tokens)
    assert eng.verify_compile_count == 1


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_spec_eos_truncation_matches_non_spec():
    """EOS inside an accepted draft run must end the request exactly
    where sequential decode would."""
    m = _tiny_model()
    prompt = np.asarray([7, 8, 9], np.int32)
    base, _ = _run_sched(m, [prompt], spec_k=0, max_new=50)
    eos = int(base[0].tokens[1])    # a token greedy decode actually emits
    b2, _ = _run_sched(m, [prompt], spec_k=0, max_new=50, eos=eos)
    s2, _ = _run_sched(m, [prompt], spec_k=4, max_new=50, eos=eos)
    assert s2[0].finish_reason == b2[0].finish_reason == "eos"
    np.testing.assert_array_equal(s2[0].tokens, b2[0].tokens)


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_spec_int8_composed_greedy_matches_int8_decode():
    """Both levers at once: spec over the int8 pool must equal the int8
    non-spec stream bit-for-bit (same quantized cache math, greedy)."""
    m = _tiny_model()
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 512, (12,)) for _ in range(3)]
    base, _ = _run_sched(m, prompts, spec_k=0, kv_dtype="int8")
    spec, eng = _run_sched(m, prompts, spec_k=4, kv_dtype="int8")
    assert [list(r.tokens) for r in spec] == \
        [list(r.tokens) for r in base]
    assert eng.verify_compile_count == 1
    assert str(eng.cache.k.dtype) == "int8"


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_spec_near_max_len_caps_acceptance_in_program():
    """A slot whose remaining capacity is smaller than k: acceptance is
    clamped in-program (no garbage logits past the cache cap) and the
    request retires cache_full with the same tokens as non-spec."""
    m = _tiny_model()
    prompt = np.random.default_rng(19).integers(0, 512, (28,))
    base, _ = _run_sched(m, [prompt], spec_k=0, max_new=50, max_len=32)
    spec, _ = _run_sched(m, [prompt], spec_k=4, max_new=50, max_len=32)
    assert base[0].finish_reason == spec[0].finish_reason == "cache_full"
    np.testing.assert_array_equal(spec[0].tokens, base[0].tokens)


# ---------------------------------------------------------------------------
# accept-rate extremes + compile stability
# ---------------------------------------------------------------------------

@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_compile_once_across_accept_rate_extremes():
    """All-accept and all-reject verify steps are traced-value paths of
    ONE program: feeding perfect drafts and adversarial garbage drafts
    must not add programs to the verify jit (nor touch decode's)."""
    m = _tiny_model()
    eng = _engine(m, spec_k=3)
    p = np.random.default_rng(23).integers(0, 512, (8,))
    tok, _ = eng.prefill(0, p, temperature=0.0)
    # sequential greedy reference to construct PERFECT drafts
    ref = _engine(m, spec_k=0)
    rtok, _ = ref.prefill(0, p, temperature=0.0)
    greedy = [rtok]
    for _ in range(6):
        nt, _ = ref.decode([greedy[-1], 0], [True, False], [0.0, 0.0],
                           [0, 0], [1.0, 1.0])
        greedy.append(int(nt[0]))
    # all-accept: the true continuation as the draft
    emitted, counts, _ = eng.decode_spec(
        [tok, 0], np.asarray([greedy[1:4], [0, 0, 0]]), [True, False],
        [0.0, 0.0], [0, 0], [1.0, 1.0])
    assert int(counts[0]) == 4            # 3 accepted + bonus
    assert list(emitted[0, :4]) == greedy[1:5]
    # all-reject: garbage drafts — exactly ONE (corrected) token emitted
    emitted, counts, _ = eng.decode_spec(
        [greedy[4], 0], np.full((2, 3), 511, np.int32), [True, False],
        [0.0, 0.0], [0, 0], [1.0, 1.0])
    assert int(counts[0]) == 1
    assert int(emitted[0, 0]) == greedy[5]
    assert eng.verify_compile_count == 1, \
        "accept-rate extremes added a verify program"
    assert eng.decode_compile_count == 0  # fallback untouched in this run
    # host mirror tracked the in-program rollbacks: 8 prompt + 4 + 1
    assert int(eng.slot_lengths()[0]) == int(p.size) + 5


def test_spec_requires_paged_engine():
    with pytest.raises(ValueError, match="paged"):
        _engine(paged=False, spec_k=2)


def test_verify_hlo_has_no_s64_compute():
    import re

    import jax
    from paddle_tpu.analysis import S64_COMPUTE_OPS
    from paddle_tpu.core.dtype import x64_scope
    eng = _engine(spec_k=4, kv_dtype="int8")
    with x64_scope(False):
        lowered = jax.jit(
            eng._verify_fn,
            donate_argnums=eng._verify_donate_argnums).lower(
            *eng.verify_trace_args())
    hlo = lowered.compile().as_text()
    assert "f64[" not in hlo
    for op in S64_COMPUTE_OPS:
        pat = re.compile(r"s64\[[0-9,]*\]\S* " + op + r"\(")
        assert not pat.search(hlo), "s64 %s leaked into spec verify" % op


# ---------------------------------------------------------------------------
# seed reproducibility + sampled-path exactness plumbing
# ---------------------------------------------------------------------------

def test_generate_seed_reproducible_with_spec_on_cached_engine():
    from paddle_tpu.serving import generate
    m = _tiny_model(seed=3)
    prompt = np.random.default_rng(83).integers(0, 512, (40,))
    kw = dict(max_new_tokens=8, temperature=1.0, seed=0, max_len=64,
              page_size=16, spec_k=4)
    a = generate(m, prompt, **kw)
    b = generate(m, prompt, **kw)     # same CACHED engine, same seed
    np.testing.assert_array_equal(a[0], b[0])
    c = generate(m, prompt, **dict(kw, seed=1))
    assert not np.array_equal(a[0], c[0])
    # spec_k is engine geometry: one engine, one verify program
    (key, eng), = m.__dict__["_serving_engines"].items()
    assert eng.verify_compile_count == 1


def test_spec_accept_unit_semantics():
    """spec_accept over synthetic logits: greedy accept/reject/bonus and
    the max_accept clamp, without a model in the loop."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.sampling import spec_accept
    V, S, k = 8, 2, 3
    # greedy chain: argmax at position j is j+1
    logits = np.full((S, k + 1, V), -10.0, np.float32)
    for j in range(k + 1):
        logits[:, j, j + 1] = 10.0
    greedy = jnp.zeros((S,), jnp.float32)   # temperature 0
    key = jax.random.key(0)
    args = (greedy, jnp.zeros((S,), jnp.int32), jnp.ones((S,), jnp.float32))
    # slot 0: perfect draft [1,2,3]; slot 1: diverges at position 1
    toks = jnp.asarray([[0, 1, 2, 3], [0, 1, 9, 3]], jnp.int32)
    emitted, counts = spec_accept(jnp.asarray(logits), toks, key, *args)
    assert list(np.asarray(counts)) == [4, 2]
    assert list(np.asarray(emitted)[0, :4]) == [1, 2, 3, 4]
    # slot 1 accepted d1=1, then the correction at position 1 is its
    # greedy argmax (2); everything beyond is zero-padded
    assert list(np.asarray(emitted)[1, :2]) == [1, 2]
    assert list(np.asarray(emitted)[1, 2:]) == [0, 0]
    # max_accept clamps acceptance (cache-capacity rollback): cap 1
    emitted, counts = spec_accept(
        jnp.asarray(logits), toks, key, *args,
        max_accept=jnp.asarray([1, 1], jnp.int32))
    assert list(np.asarray(counts)) == [2, 2]
    assert list(np.asarray(emitted)[0, :2]) == [1, 2]
    # REGRESSION (review find): a capacity clamp is NOT a rejection —
    # the correction token at the cap must still be able to equal the
    # (accepted-but-uncommittable) draft token.  top_k=1 + p~1 on the
    # draft makes the old behavior observable: masking the draft out of
    # the resample left an all--inf residual and emitted garbage.
    sampled = (jnp.ones((S,), jnp.float32),          # temperature 1
               jnp.ones((S,), jnp.int32),            # top_k = 1
               jnp.ones((S,), jnp.float32))
    toks_p = jnp.asarray([[0, 1, 2, 3], [0, 1, 2, 3]], jnp.int32)
    emitted, counts = spec_accept(
        jnp.asarray(logits), toks_p, key, *sampled,
        max_accept=jnp.asarray([0, 0], jnp.int32))
    assert list(np.asarray(counts)) == [1, 1]
    # position 0's filtered distribution is a point mass on token 1 (the
    # argmax) — the emitted correction must be that token, not argmax of
    # an all-masked row
    assert list(np.asarray(emitted)[:, 0]) == [1, 1]
    # a REAL rejection still excludes the rejected draft: slot draft 9
    # at position 1 (p~0 under the chain) rejects, and the correction
    # cannot be 9
    toks_r = jnp.asarray([[0, 1, 9, 3], [0, 1, 9, 3]], jnp.int32)
    emitted, counts = spec_accept(jnp.asarray(logits), toks_r, key,
                                  *sampled)
    assert (np.asarray(emitted)[np.arange(S),
                                np.asarray(counts) - 1] != 9).all()


def test_prompt_lookup_propose_units():
    from paddle_tpu.serving.spec import propose
    h = np.asarray([5, 6, 7, 1, 2, 5, 6, 7], np.int32)
    draft, hit = propose(h, 3, max_ngram=3)
    assert hit and list(draft) == [1, 2, 5]   # continuation of [5,6,7]
    # most RECENT match wins
    h2 = np.asarray([1, 2, 9, 1, 2, 4, 1, 2], np.int32)
    draft, hit = propose(h2, 2, max_ngram=2)
    assert hit and list(draft) == [4, 1]
    # no match: pads with the last token, hit False
    draft, hit = propose(np.asarray([3, 1, 4], np.int32), 2)
    assert not hit and list(draft) == [4, 4]
    # degenerate histories never crash
    assert propose(np.asarray([9], np.int32), 2)[0].shape == (2,)
    assert propose(np.asarray([], np.int32), 2)[0].shape == (2,)


def test_request_result_reports_spec_counter_pair():
    from paddle_tpu import observability as obs
    m = _tiny_model()
    prompts = [np.random.default_rng(29).integers(0, 512, (10,))]
    prop0 = obs.counter("serving.spec_proposed_tokens").value
    acc0 = obs.counter("serving.spec_accepted_tokens").value
    # sync loop: the exact per-request == engine-stats == counter
    # identities below hold only without the ISSUE-13 overlapped loop's
    # overshoot verify step (engine spec_stats meter DEVICE work, so an
    # overshoot step dispatched for a since-retired slot counts there
    # but is — correctly — never credited to the request)
    res, eng = _run_sched(m, prompts, spec_k=4, max_new=9, overlap=False)
    r = res[0]
    assert r.finish_reason == "length" and r.tokens.size == 9
    # one slot, k proposals per verify step
    assert r.spec_proposed == 4 * eng.spec_stats["steps"] > 0
    # accepted is bounded by proposed; NOTE it counts in-program
    # acceptance, which can exceed the HOST-side truncation at the
    # max_new_tokens budget (the surplus rows were rolled into the cache
    # but the request retired) — so no exact token-count identity here
    assert 0 <= r.spec_accepted <= r.spec_proposed
    assert obs.counter("serving.spec_proposed_tokens").value - prop0 \
        == eng.spec_stats["proposed"] == r.spec_proposed
    assert obs.counter("serving.spec_accepted_tokens").value - acc0 \
        == eng.spec_stats["accepted"] == r.spec_accepted


def test_kv_quant_error_gauge_opt_in(monkeypatch):
    from paddle_tpu import observability as obs
    monkeypatch.setenv("PADDLE_TPU_METRICS_KV_QUANT_ERROR", "1")
    m = _tiny_model()
    eng = _engine(m, kv_dtype="int8")
    p = np.random.default_rng(31).integers(0, 512, (6,))
    tok, _ = eng.prefill(0, p, temperature=0.0)
    eng.decode([tok, 0], [True, False], [0.0, 0.0], [0, 0], [1.0, 1.0])
    err = obs.gauge("serving.kv_quant_error").value
    assert 0.0 < err < 0.5, \
        "kv_quant_error gauge not plausible: %r" % err
    # off by default: a fresh engine without the env var never syncs
    monkeypatch.delenv("PADDLE_TPU_METRICS_KV_QUANT_ERROR")
    eng2 = _engine(m, kv_dtype="int8")
    assert eng2._track_qerr is False


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_kv_bytes_per_token_halved_under_int8():
    """The bench acceptance line at engine level: per-token decode KV
    bytes under int8 are <= 0.55x the unquantized bf16-equivalent —
    here vs the f32 pool, whose ratio (d+4)/(4d) is even smaller; the
    bf16 ratio (d+4)/(2d) is asserted arithmetically at bench head_dim."""
    m = _tiny_model()
    rng = np.random.default_rng(37)
    p = [rng.integers(0, 512, (6,)), rng.integers(0, 512, (9,))]

    def drive(kv_dtype):
        eng = _engine(m, kv_dtype=kv_dtype)
        toks = []
        for i, pr in enumerate(p):
            t, _ = eng.prefill(i, pr, temperature=0.0)
            toks.append(t)
        for _ in range(4):
            nt, _ = eng.decode(toks, [True, True], [0.0, 0.0], [0, 0],
                               [1.0, 1.0])
            toks = [int(nt[0]), int(nt[1])]
        return eng.kv_bytes_per_token()

    b = drive(None)
    q = drive("int8")
    assert q["paged"] / b["paged"] <= 0.55
    assert q["flat"] / b["flat"] <= 0.55
    # at the bench's head_dim 64, the int8-vs-bf16 row ratio is the
    # acceptance bound: (64 + 4) / (2 * 64) = 0.53 <= 0.55
    assert (64 + 4) / (2 * 64) <= 0.55
