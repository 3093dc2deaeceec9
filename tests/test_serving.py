"""Serving engine: static slotted KV cache + continuous-batching decode.

Covers the ISSUE-5 acceptance criteria:
* logits parity of slotted-cache decode vs full-forward recompute at
  every position (engine path and model-level path, both layer layouts);
* the decode step compiles EXACTLY ONCE across 32 generated tokens over
  concurrent sequences AND across slot admission/eviction (jit
  cache-miss counter);
* scheduler unit behavior: FIFO admission order, prefill bucket
  selection, eviction on EOS / max_new_tokens / cache_full;
* sampling bugfix sweep: top-p keeps >= 1 token, top-k stays int32
  under the global x64 flag, sampling consumes a THREADED key (the
  global RNG stream does not shift);
* the legacy concat cache survives as an explicitly-named shim.
"""
import re

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


# ---------------------------------------------------------------------------
# KV-cache / decode correctness
# ---------------------------------------------------------------------------

def test_gen_cache_is_static_slotted():
    from paddle_tpu.serving.cache import SlottedKVCache
    m = _tiny_model()
    cache = m.gen_cache(3, max_len=32)
    assert isinstance(cache, SlottedKVCache)
    assert cache.k.shape == (3, 2, 32, 4, 16)   # (slots, L, T, H, D)
    assert cache.lengths.shape == (3,) and str(
        cache.lengths.dtype) == "int32"


def test_model_level_slotted_decode_parity():
    m = _tiny_model()
    ids = np.random.default_rng(3).integers(0, 512, (1, 8)).astype("int32")
    full = m(paddle.to_tensor(ids)).numpy()
    cache = m.gen_cache(1, max_len=64)
    outs = []
    for t in range(8):
        logit, cache = m(paddle.to_tensor(ids[:, t:t + 1]), cache=cache)
        outs.append(logit.numpy())
    np.testing.assert_allclose(np.concatenate(outs, axis=1), full,
                               rtol=3e-4, atol=3e-4)
    assert int(np.asarray(cache.lengths)[0]) == 8


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_model_level_batched_prefill_then_decode():
    # a bare SlottedKVCache accepts multi-token appends: whole-prompt
    # "prefill as a batch" then per-token decode, all through model(x,
    # cache=...)
    m = _tiny_model()
    ids = np.random.default_rng(5).integers(0, 512, (2, 6)).astype("int32")
    full = m(paddle.to_tensor(ids)).numpy()
    cache = m.gen_cache(2, max_len=32)
    logits, cache = m(paddle.to_tensor(ids), cache=cache)
    np.testing.assert_allclose(logits.numpy(), full, rtol=3e-4, atol=3e-4)
    assert list(np.asarray(cache.lengths)) == [6, 6]
    tok = np.asarray([[1], [2]], np.int32)
    l2, cache = m(paddle.to_tensor(tok), cache=cache)
    ref = [_full_last_logits(m, list(ids[b]) + [int(tok[b, 0])])
           for b in range(2)]
    np.testing.assert_allclose(l2.numpy()[:, 0], np.stack(ref),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_engine_decode_parity_every_position():
    from paddle_tpu.serving.engine import DecodeEngine
    m = _tiny_model()
    eng = DecodeEngine(m, num_slots=2, max_len=64, seed=1)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, (5,)), rng.integers(0, 512, (9,))]
    seqs = []
    for i, p in enumerate(prompts):
        tok, logits = eng.prefill(i, p, temperature=0.0)
        np.testing.assert_allclose(np.asarray(logits),
                                   _full_last_logits(m, p),
                                   rtol=2e-4, atol=2e-4)
        seqs.append(list(p) + [tok])
    for _ in range(6):
        toks = [s[-1] for s in seqs]
        nt, logits = eng.decode(toks, [True, True], [0.0, 0.0], [0, 0],
                                [1.0, 1.0])
        for b in range(2):
            np.testing.assert_allclose(
                np.asarray(logits[b]), _full_last_logits(m, seqs[b]),
                rtol=2e-4, atol=2e-4)
            seqs[b].append(int(nt[b]))
    assert eng.decode_compile_count == 1


def test_decode_attention_variants_parity():
    import jax.numpy as jnp
    from paddle_tpu.kernels import decode_attention as da
    rng = np.random.default_rng(0)
    B, T, H, D = 3, 64, 2, 8
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    pos = jnp.asarray([0, 17, 63], jnp.int32)
    ref = da._masked(q, k, v, pos, None)
    # per-slot numpy reference over the ragged valid prefixes
    for b in range(B):
        n = int(pos[b])
        lg = np.einsum("qhd,thd->hqt", np.asarray(q[b]),
                       np.asarray(k[b, :n + 1])) / np.sqrt(D)
        p = np.exp(lg - lg.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        exp = np.einsum("hqt,thd->qhd", p, np.asarray(v[b, :n + 1]))
        np.testing.assert_allclose(np.asarray(ref[b]), exp,
                                   rtol=1e-5, atol=1e-5)
    for bt in da.supported_block_ts(T):
        out = da._chunked(q, k, v, pos, None, bt)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# compile stability (the structural claim)
# ---------------------------------------------------------------------------

def test_decode_compiles_once_across_32_tokens_and_slot_churn():
    from paddle_tpu.serving.engine import DecodeEngine
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    m = _tiny_model()
    eng = DecodeEngine(m, num_slots=2, max_len=64, seed=0)
    sched = ContinuousBatchingScheduler(eng)
    rng = np.random.default_rng(2)
    # 5 requests through 2 slots: admission + eviction churn mid-run;
    # varied sampling params per request (traced args, not static)
    for i in range(5):
        sched.submit(Request(prompt=rng.integers(0, 512, (3 + 2 * i,)),
                             max_new_tokens=8,
                             temperature=float(i % 3) * 0.5,
                             top_k=(0, 5, 40)[i % 3],
                             top_p=(1.0, 0.9, 0.3)[i % 3]))
    results = sched.run()
    total = sum(r.tokens.size for r in results.values())
    assert total == 5 * 8
    assert total >= 32
    assert eng.decode_compile_count == 1, \
        "decode retraced: %d programs" % eng.decode_compile_count
    # paged (default) engines run ONE chunked-prefill program, full stop
    assert eng.prefill_compile_count == 1


def test_decode_step_hlo_has_no_s64_compute():
    # same leak definition as tests/test_x64_audit.py: s64 inputs are
    # fine under global x64, s64 COMPUTE is the leak (int32-safe decode)
    import jax
    from paddle_tpu.analysis import S64_COMPUTE_OPS
    from paddle_tpu.core.dtype import x64_scope
    from paddle_tpu.serving.engine import DecodeEngine
    m = _tiny_model()
    eng = DecodeEngine(m, num_slots=2, max_len=64)
    with x64_scope(False):   # the engine's production trace scope
        lowered = jax.jit(eng._decode_fn,
                          donate_argnums=eng._decode_donate_argnums).lower(
            *eng.decode_trace_args())
    hlo = lowered.compile().as_text()
    assert "f64[" not in hlo
    for op in S64_COMPUTE_OPS:
        pat = re.compile(r"s64\[[0-9,]*\]\S* " + op + r"\(")
        assert not pat.search(hlo), "s64 %s leaked into decode step" % op


def test_serving_programs_registered_for_audit():
    from paddle_tpu.analysis.trace.programs import builder_names
    names = builder_names()
    assert "serving" in names and "gpt_decode" in names


# ---------------------------------------------------------------------------
# scheduler units
# ---------------------------------------------------------------------------

def _engine(num_slots=2, max_len=64, **kw):
    from paddle_tpu.serving.engine import DecodeEngine
    return DecodeEngine(_tiny_model(), num_slots=num_slots,
                        max_len=max_len, **kw)


def test_scheduler_admission_is_fifo():
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    eng = _engine(num_slots=2)
    sched = ContinuousBatchingScheduler(eng)
    rids = [sched.submit(Request(prompt=np.asarray([i + 1], np.int32),
                                 max_new_tokens=4)) for i in range(4)]
    sched.admit()
    active = [a.req.rid for a in sched.slots if a is not None]
    assert active == rids[:2]              # first two submitted, in order
    assert [r.rid for r in sched.waiting] == rids[2:]
    # drain one slot -> the NEXT waiting request (rids[2]) takes it
    # (paged admissions stay `prefilling` until their chunks run, so
    # the drive loop must advance prefill too — step() without admit)
    while sched.slots[0] is not None or sched.slots[1] is not None:
        sched.prefill_once()
        sched.decode_once()
        if any(a is None for a in sched.slots):
            break
    sched.admit()
    newly = [a.req.rid for a in sched.slots if a is not None]
    assert rids[2] in newly


def test_prefill_bucket_selection():
    # bucketed prefill is the SLOTTED path (paged engines compile one
    # chunk program instead — tests/test_paged.py)
    eng = _engine(num_slots=1, max_len=64, min_bucket=16, paged=False)
    assert eng.buckets == [16, 32, 64]
    assert eng.bucket_for(1) == 16
    assert eng.bucket_for(16) == 16
    assert eng.bucket_for(17) == 32
    assert eng.bucket_for(64) == 64
    with pytest.raises(ValueError):
        eng.bucket_for(65)
    # distinct buckets = distinct compiles; repeats hit the jit cache
    rng = np.random.default_rng(0)
    eng2 = _engine(num_slots=1, max_len=64, paged=False)
    for n in (4, 10, 16):                  # all bucket 16
        eng2.prefill(0, rng.integers(0, 512, (n,)))
    assert eng2.prefill_compile_count == 1
    eng2.prefill(0, rng.integers(0, 512, (20,)))   # bucket 32
    assert eng2.prefill_compile_count == 2


def test_scheduler_eviction_on_eos_and_budget():
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    eng = _engine(num_slots=2)
    # find a token the greedy tiny model actually emits, use it as "EOS"
    probe = ContinuousBatchingScheduler(eng)
    rid = probe.submit(Request(prompt=np.asarray([7, 8, 9], np.int32),
                               max_new_tokens=3, temperature=0.0))
    eos = int(probe.run()[rid].tokens[1])
    eng.reset()
    sched = ContinuousBatchingScheduler(eng)
    r_eos = sched.submit(Request(prompt=np.asarray([7, 8, 9], np.int32),
                                 max_new_tokens=50, temperature=0.0,
                                 eos_token_id=eos))
    r_len = sched.submit(Request(prompt=np.asarray([1, 2], np.int32),
                                 max_new_tokens=4, temperature=0.0))
    res = sched.run()
    assert res[r_eos].finish_reason == "eos"
    assert res[r_eos].tokens[-1] == eos
    assert res[r_eos].tokens.size < 50
    assert res[r_len].finish_reason == "length"
    assert res[r_len].tokens.size == 4


def test_scheduler_eviction_on_cache_full():
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    eng = _engine(num_slots=1, max_len=16, min_bucket=8)
    sched = ContinuousBatchingScheduler(eng)
    rid = sched.submit(Request(prompt=np.asarray([1, 2, 3, 4, 5], np.int32),
                               max_new_tokens=100, temperature=0.0))
    res = sched.run()
    assert res[rid].finish_reason == "cache_full"
    # prefill sets length to the REAL 5 tokens and samples the first
    # generated token; each decode then writes the previous token before
    # sampling the next, so the cache fills after max_len - prompt
    # decodes and the final sampled token is never written: the request
    # carries (16 - 5) + 1 generated tokens
    assert res[rid].tokens.size == 16 - 5 + 1
    # retirement frees the slot: its pages return to the pool and the
    # host length zeroes (slotted engines used to leave the stale
    # length; the paged allocator reclaims eagerly)
    assert int(eng.slot_lengths()[0]) == 0
    assert eng.pages_free() == eng.num_pages


def test_scheduler_reports_ttft_tpot():
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    eng = _engine(num_slots=1)
    sched = ContinuousBatchingScheduler(eng)
    rid = sched.submit(Request(prompt=np.asarray([3, 1], np.int32),
                               max_new_tokens=5))
    res = sched.run()[rid]
    assert res.ttft > 0.0 and res.tpot > 0.0


# ---------------------------------------------------------------------------
# sampling bugfix sweep
# ---------------------------------------------------------------------------

def test_top_p_keeps_at_least_one_token():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.sampling import apply_top_p, sample
    logits = jnp.asarray([[1.0, 3.0, 2.0, -1.0]], jnp.float32)
    for p in (0.0, 1e-6, 0.3):
        out = apply_top_p(logits, jnp.asarray([p], jnp.float32))
        kept = np.asarray(out > -1e29).sum()
        assert kept >= 1, "top_p=%r filtered out everything" % p
        # the survivor must be the argmax
        assert np.asarray(out)[0, 1] > -1e29
    # p==0 must still SAMPLE the top token (not nan/garbage)
    tok = sample(logits, jax.random.key(0),
                 jnp.asarray([0.7], jnp.float32),
                 jnp.asarray([0], jnp.int32), jnp.asarray([0.0], jnp.float32))
    assert int(tok[0]) == 1


def test_top_p_mass_cutoff():
    import jax.numpy as jnp
    from paddle_tpu.serving.sampling import apply_top_p
    # probs ~ [0.643, 0.237, 0.087, 0.032] for logits [3,2,1,0]
    logits = jnp.asarray([[3.0, 2.0, 1.0, 0.0]], jnp.float32)
    out = np.asarray(apply_top_p(logits, jnp.asarray([0.7], jnp.float32)))
    # mass before token1 is 0.643 < 0.7 -> kept; before token2 is 0.88 -> cut
    assert (out > -1e29).tolist() == [[True, True, False, False]]
    out = np.asarray(apply_top_p(logits, jnp.asarray([1.0], jnp.float32)))
    assert (out > -1e29).all()             # disabled


def test_top_k_is_int32_safe_and_correct():
    import jax.numpy as jnp
    from paddle_tpu.serving.sampling import apply_top_k
    logits = jnp.asarray([[5.0, 1.0, 4.0, 3.0, 2.0],
                          [5.0, 1.0, 4.0, 3.0, 2.0]], jnp.float32)
    out = np.asarray(apply_top_k(
        logits, jnp.asarray([2, 0], jnp.int32), k_max=4))
    assert (out[0] > -1e29).tolist() == [True, False, True, False, False]
    assert (out[1] > -1e29).all()          # 0 disables
    # k beyond k_max clamps to k_max, not crash
    out = np.asarray(apply_top_k(
        logits, jnp.asarray([99, 99], jnp.int32), k_max=3))
    assert (out[0] > -1e29).sum() == 3


def test_sampled_tokens_are_int32():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.sampling import sample
    logits = jnp.asarray(np.random.default_rng(0).standard_normal(
        (3, 16)), jnp.float32)
    tok = sample(logits, jax.random.key(1),
                 jnp.asarray([0.0, 1.0, 0.5], jnp.float32),
                 jnp.asarray([0, 4, 0], jnp.int32),
                 jnp.asarray([1.0, 0.9, 1.0], jnp.float32))
    assert str(tok.dtype) == "int32"
    assert int(tok[0]) == int(np.argmax(np.asarray(logits[0])))  # greedy


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_sampling_uses_threaded_key_not_global_stream():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core import random as rnd
    from paddle_tpu.serving.sampling import sample
    logits = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 32)), jnp.float32)
    args = (jnp.asarray([1.0, 1.0], jnp.float32),
            jnp.asarray([0, 0], jnp.int32),
            jnp.asarray([1.0, 1.0], jnp.float32))
    before = rnd.get_rng_state()
    t1 = sample(logits, jax.random.key(7), *args)
    assert rnd.get_rng_state() == before, \
        "sampling shifted the global RNG stream"
    t2 = sample(logits, jax.random.key(7), *args)
    assert (np.asarray(t1) == np.asarray(t2)).all()   # key-deterministic
    # engine threads fold_in(base, step): two engines with one seed agree
    from paddle_tpu.serving.engine import DecodeEngine
    m = _tiny_model()
    outs = []
    for _ in range(2):
        eng = DecodeEngine(m, num_slots=1, max_len=32, seed=5)
        tok, _ = eng.prefill(0, np.asarray([3, 1, 4], np.int32),
                             temperature=1.0)
        seq = [tok]
        for _ in range(4):
            nt, _ = eng.decode([seq[-1]], [True], [1.0], [0], [1.0])
            seq.append(int(nt[0]))
        outs.append(seq)
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# integration surfaces
# ---------------------------------------------------------------------------

def test_model_generate_routes_through_engine():
    m = _tiny_model()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, (4,)), rng.integers(0, 512, (7,))]
    outs = m.generate(prompts, max_new_tokens=6, greedy=True, max_len=32)
    assert len(outs) == 2
    for p, o in zip(prompts, outs):
        assert o.shape == (6,) and str(o.dtype) == "int32"
        # greedy == argmax of the full-forward recompute, step by step
        seq = list(p)
        for tok in o:
            assert int(tok) == int(np.argmax(_full_last_logits(m, seq)))
            seq.append(int(tok))
    # engine is cached on the model: a second call reuses the compiled
    # decode program
    eng = m.__dict__["_serving_engines"]
    (key, engine), = eng.items()
    m.generate(prompts, max_new_tokens=3, greedy=True, max_len=32)
    assert engine.decode_compile_count == 1


def test_predictor_generate_model_backed():
    from paddle_tpu.inference import create_predictor
    m = _tiny_model()
    pred = create_predictor(model=m)
    outs = pred.generate(np.asarray([[5, 6, 7]], np.int32),
                         max_new_tokens=4, temperature=0.0, max_len=32)
    assert len(outs) == 1 and outs[0].shape == (4,)
    seq = [5, 6, 7]
    for tok in outs[0]:
        assert int(tok) == int(np.argmax(_full_last_logits(m, seq)))
        seq.append(int(tok))


def test_predictor_generate_artifact_backed_raises():
    from paddle_tpu.inference import Predictor, create_predictor
    with pytest.raises(ValueError):
        Predictor()                        # neither config nor model
    # artifact-only surfaces on a model-backed predictor fail LOUDLY,
    # naming the reason — not with a raw AttributeError/KeyError
    pred = create_predictor(model=_tiny_model())
    for fn in (pred.run, pred.get_input_names, pred.get_output_names,
               lambda: pred.get_input_handle("x"),
               lambda: pred.get_output_handle("y")):
        with pytest.raises(RuntimeError, match="artifact-backed"):
            fn()


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_generate_prompt_shapes():
    # a flat 1-D prompt (list OR array OR Tensor) is ONE prompt, never N
    # single-token prompts; 2-D Tensors row-split like 2-D arrays
    m = _tiny_model()
    flat_list = m.generate([5, 6, 7], max_new_tokens=3, greedy=True,
                           max_len=32)
    flat_np = m.generate(np.asarray([5, 6, 7]), max_new_tokens=3,
                         greedy=True, max_len=32)
    flat_t = m.generate(paddle.to_tensor(np.asarray([5, 6, 7], np.int32)),
                        max_new_tokens=3, greedy=True, max_len=32)
    assert len(flat_list) == len(flat_np) == len(flat_t) == 1
    np.testing.assert_array_equal(flat_list[0], flat_np[0])
    np.testing.assert_array_equal(flat_list[0], flat_t[0])
    two_d = m.generate(paddle.to_tensor(
        np.asarray([[5, 6, 7], [7, 6, 5]], np.int32)),
        max_new_tokens=3, greedy=True, max_len=32)
    assert len(two_d) == 2 and two_d[0].dtype == np.int32
    np.testing.assert_array_equal(two_d[0], flat_list[0])


def test_generate_restores_training_mode():
    # generate() between training epochs must not silently flip the
    # model to eval (dropout off) for the rest of the run
    m = _tiny_model()
    m.train()
    m.generate([5, 6], max_new_tokens=2, greedy=True, max_len=32)
    assert m.training is True
    m.eval()
    m.generate([5, 6], max_new_tokens=2, greedy=True, max_len=32)
    assert m.training is False


def test_generate_seed_is_reproducible_on_cached_engine():
    m = _tiny_model()
    kw = dict(max_new_tokens=6, temperature=1.0, max_len=32, seed=3)
    a = m.generate([4, 2], **kw)
    b = m.generate([4, 2], **kw)          # same CACHED engine, same seed
    np.testing.assert_array_equal(a[0], b[0])
    # and the seed is not engine geometry: no second engine was built
    assert len(m.__dict__["_serving_engines"]) == 1
    c = m.generate([4, 2], max_new_tokens=6, temperature=1.0, max_len=32,
                   seed=4)
    assert len(m.__dict__["_serving_engines"]) == 1
    assert not np.array_equal(a[0], c[0])


def test_non_power_of_two_max_len_gets_a_final_bucket():
    from paddle_tpu.serving.engine import prefill_buckets_for
    assert prefill_buckets_for(100) == [16, 32, 64, 100]
    assert prefill_buckets_for(64) == [16, 32, 64]
    eng = _engine(num_slots=1, max_len=48, min_bucket=16, paged=False)
    assert eng.buckets == [16, 32, 48]
    assert eng.bucket_for(40) == 48       # fits the cache -> admissible
    tok, _ = eng.prefill(0, np.arange(1, 41, dtype=np.int32))
    assert int(eng.slot_lengths()[0]) == 40


def test_engine_cache_is_bounded_and_bucketed():
    from paddle_tpu import serving
    m = _tiny_model()
    # 1..3 prompts bucket to 1/2/4 slots: three geometries, reused later
    for n in (1, 2, 3, 2, 1):
        m.generate([np.asarray([1, 2])] * n, max_new_tokens=1,
                   greedy=True, max_len=32)
    cache = m.__dict__["_serving_engines"]
    assert len(cache) == 3
    slots = sorted(k[0] for k in cache)
    assert slots == [1, 2, 4]
    # the LRU bound holds even under hostile geometry churn
    for ns in (3, 5, 6, 7):
        serving.engine_for(m, num_slots=ns, max_len=32)
    assert len(cache) <= serving._MAX_CACHED_ENGINES


# ---------------------------------------------------------------------------
# legacy shim
# ---------------------------------------------------------------------------

@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_legacy_concat_cache_shim_still_decodes():
    m = _tiny_model()
    ids = np.random.default_rng(7).integers(0, 512, (1, 6)).astype("int32")
    full = m(paddle.to_tensor(ids)).numpy()
    cache = m.gen_legacy_concat_cache(1)
    outs = []
    for t in range(6):
        logit, cache = m(paddle.to_tensor(ids[:, t:t + 1]), cache=cache)
        outs.append(logit.numpy())
    np.testing.assert_allclose(np.concatenate(outs, axis=1), full,
                               rtol=3e-4, atol=3e-4)
    # and its shape GROWS per token — the recompile-per-token behavior
    # the slotted cache exists to kill (kept only as a compat shim)
    assert cache[0][0].shape[1] == 6
