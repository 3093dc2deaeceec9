"""Paged KV cache (ISSUE 7): page pool + page table + prefix sharing +
chunked prefill.

Covers the acceptance criteria:
* paged-vs-slotted greedy decode is BIT-identical, and paged decode
  logits match a full-forward recompute at every position;
* prefix-sharing correctness under copy-on-write: an admission that
  maps another request's pages never recomputes them, and mutating one
  sharer (its decode appends) never perturbs the other's logits;
* chunked prefill: a long admission runs as fixed-size chunks
  interleaved with decode (TPOT non-interference — the in-flight
  request keeps generating between chunks), all through ONE compiled
  chunk program;
* compile-once across all of the above (slot churn, prefix hits,
  chunked admissions, copy-on-write);
* refcount-aware eviction: under a prefix-heavy workload the victim is
  the slot with the most UNSHARED pages, not bare FIFO;
* PageAllocator units: free list, refcounts, hash-chained prefix
  lookup, free-but-cached reclaim, copy-on-write bookkeeping.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving.pages import PageAllocator, PagePoolExhausted


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
# PageAllocator units (host-side, no jax)
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_roundtrip():
    al = PageAllocator(num_pages=4, num_slots=2, max_pages=4, page_size=8)
    pids = [al.alloc() for _ in range(4)]
    assert sorted(pids) == [0, 1, 2, 3]
    for i, p in enumerate(pids):
        al.map(0, i, p)
    assert al.pages_free() == 0 and al.slot_pages(0) == 4
    with pytest.raises(PagePoolExhausted):
        al.alloc()
    al.free_slot(0)
    assert al.pages_free() == 4 and al.slot_pages(0) == 0


def test_allocator_refcounts_and_unshared():
    al = PageAllocator(num_pages=4, num_slots=2, max_pages=4, page_size=8)
    p0, p1 = al.alloc(), al.alloc()
    al.map(0, 0, p0)
    al.map(0, 1, p1)
    al.share(1, 0, p0)                       # slot 1 shares slot 0's page
    assert int(al.refcount[p0]) == 2 and int(al.refcount[p1]) == 1
    assert al.unshared_pages(0) == 1         # only p1 is private
    assert al.unshared_pages(1) == 0         # everything it maps is shared
    assert al.needs_cow(1, 0) and al.needs_cow(0, 0)
    assert not al.needs_cow(0, 1)
    al.free_slot(1)                          # drops the reference only
    assert int(al.refcount[p0]) == 1 and al.unshared_pages(0) == 2


def test_allocator_prefix_chain_hash():
    al = PageAllocator(num_pages=8, num_slots=2, max_pages=4, page_size=4)
    ids = np.arange(10, dtype=np.int32)       # 2 full pages + tail of 2
    for i in range(3):
        al.map(0, i, al.alloc())
    al.register_prefix(0, ids)
    # full-prompt lookup hits everything (tail digest included)
    pages, covered = al.lookup_prefix(ids)
    assert covered == 10 and pages == [int(al.table[0, i])
                                       for i in range(3)]
    # same first 8 tokens -> the 2 full pages hit, tail differs
    other = np.concatenate([ids[:8], [99, 98]]).astype(np.int32)
    pages, covered = al.lookup_prefix(other)
    assert covered == 8 and len(pages) == 2
    # SAME page content after a DIFFERENT prefix must NOT hit (chained
    # digests: position matters, not just page bytes)
    shifted = np.concatenate([[77, 66, 55, 44], ids[:4]]).astype(np.int32)
    pages, covered = al.lookup_prefix(shifted)
    assert covered == 0 and pages == []


def test_allocator_free_but_cached_reclaim():
    al = PageAllocator(num_pages=2, num_slots=2, max_pages=2, page_size=4)
    ids = np.arange(4, dtype=np.int32)
    al.map(0, 0, al.alloc())
    al.register_prefix(0, ids)
    al.free_slot(0)
    # refcount 0 but hash-reachable: cached, still a hit
    assert al.pages_cached() == 1 and al.pages_free() == 2
    pages, covered = al.lookup_prefix(ids)
    assert covered == 4
    al.share(1, 0, pages[0])                 # revive off the cache
    assert al.pages_cached() == 0 and int(al.refcount[pages[0]]) == 1
    al.free_slot(1)
    # dry pool reclaims the cached page and purges its digests
    assert al.pages_cached() == 1
    a, b = al.alloc(), al.alloc()
    assert sorted((a, b)) == [0, 1]
    pages, covered = al.lookup_prefix(ids)
    assert covered == 0, "stale digest survived page reuse"


def test_allocator_cow_remap():
    al = PageAllocator(num_pages=4, num_slots=2, max_pages=2, page_size=4)
    p = al.alloc()
    al.map(0, 0, p)
    al.share(1, 0, p)
    fresh = al.alloc()
    old = al.remap(1, 0, fresh)
    assert old == p
    assert int(al.refcount[p]) == 1 and int(al.refcount[fresh]) == 1
    assert int(al.table[1, 0]) == fresh
    assert not al.needs_cow(0, 0) and not al.needs_cow(1, 0)


# ---------------------------------------------------------------------------
# decode correctness: paged vs slotted vs full forward
# ---------------------------------------------------------------------------

@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_model_level_paged_decode_parity():
    """model(x, cache=PagedKVCache) matches the full forward at every
    position (dense identity table — no allocator)."""
    m = _tiny_model()
    ids = np.random.default_rng(3).integers(0, 512, (1, 8)).astype("int32")
    full = m(paddle.to_tensor(ids)).numpy()
    cache = m.gen_paged_cache(1, max_len=64, page_size=16)
    assert cache.k.shape == (4, 2, 16, 4, 16)   # (pages, L, P, H, D)
    outs = []
    for t in range(8):
        logit, cache = m(paddle.to_tensor(ids[:, t:t + 1]), cache=cache)
        outs.append(logit.numpy())
    np.testing.assert_allclose(np.concatenate(outs, axis=1), full,
                               rtol=3e-4, atol=3e-4)
    assert int(np.asarray(cache.lengths)[0]) == 8


def test_paged_vs_slotted_greedy_decode_bit_identical():
    """The acceptance criterion: greedy decode over the paged engine
    emits the EXACT token sequence of the slotted engine."""
    from paddle_tpu.serving.engine import DecodeEngine
    m = _tiny_model()
    prompts = [np.random.default_rng(7).integers(0, 512, (n,))
               for n in (5, 11)]
    seqs = {}
    for paged in (False, True):
        eng = DecodeEngine(m, num_slots=2, max_len=64, seed=3,
                           paged=paged, page_size=16)
        out = []
        for i, p in enumerate(prompts):
            tok, _ = eng.prefill(i, p, temperature=0.0)
            out.append([tok])
        for _ in range(10):
            toks = [s[-1] for s in out]
            nt, _ = eng.decode(toks, [True, True], [0.0, 0.0], [0, 0],
                               [1.0, 1.0])
            for b in range(2):
                out[b].append(int(nt[b]))
        seqs[paged] = out
    assert seqs[True] == seqs[False], \
        "paged greedy decode diverged from slotted"


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_engine_paged_decode_parity_every_position():
    m = _tiny_model()
    eng = _engine(m)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, (5,)), rng.integers(0, 512, (19,))]
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


def test_paged_decode_attention_variants_parity():
    import jax.numpy as jnp
    from paddle_tpu.kernels import decode_attention as da
    rng = np.random.default_rng(0)
    B, H, D, P, MP = 3, 2, 8, 8, 8          # T = 64, pool of 32 pages
    NP = 32
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((NP, P, H, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NP, P, H, D)), jnp.float32)
    # arbitrary (non-contiguous) page mapping per slot
    table = jnp.asarray(
        rng.permutation(NP)[:B * MP].reshape(B, MP), jnp.int32)
    pos = jnp.asarray([0, 17, 63], jnp.int32)
    # reference: flatten each slot's mapped pages, run the slotted masked
    k_flat = kp[table].reshape(B, MP * P, H, D)
    v_flat = vp[table].reshape(B, MP * P, H, D)
    ref = da._masked(q, k_flat, v_flat, pos, None)
    out = da._paged_gather(q, kp, vp, table, pos, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    for m_ in da.supported_pages_per_block(MP):
        out = da._paged_chunked(q, kp, vp, table, pos, None, m_)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# prefix sharing + copy-on-write
# ---------------------------------------------------------------------------

def test_prefix_sharing_maps_pages_instead_of_recomputing():
    m = _tiny_model()
    eng = _engine(m, num_slots=2, max_len=64, page_size=8)
    sys_prompt = np.random.default_rng(11).integers(0, 512, (20,))
    tok0, _ = eng.prefill(0, sys_prompt, temperature=0.0)
    # same prompt into another slot: 2 full pages AND the partial-tail
    # digest hit — the whole prompt is cached, capped at n-1=19 tokens
    # so the final token reruns through the chunk program (that's what
    # produces the first-token logits); the shared tail page's write is
    # copy-on-written
    task = eng.prefill_begin(1, sys_prompt, temperature=0.0)
    assert task.shared_tokens == 19 and task.shared_pages == 3
    while not eng.prefill_step(task):
        pass
    assert task.chunks_run == 1          # one 1-token chunk
    assert task.first_token == tok0, \
        "prefix-hit admission sampled a different greedy first token"
    al = eng._alloc
    # full pages are the SAME pages (refcount 2)...
    for idx in range(2):
        assert int(al.table[0, idx]) == int(al.table[1, idx])
        assert int(al.refcount[al.table[0, idx]]) == 2
    # ...but the tail page was copy-on-written private before its
    # row-19 write (slot 0's copy must stay pristine)
    assert int(al.table[0, 2]) != int(al.table[1, 2])
    assert int(al.refcount[al.table[0, 2]]) == 1
    assert int(al.refcount[al.table[1, 2]]) == 1


def test_fully_cached_prompt_admits_in_one_chunk():
    m = _tiny_model()
    eng = _engine(m, num_slots=2, max_len=64, page_size=8,
                  prefill_chunk=8)
    prompt = np.random.default_rng(13).integers(0, 512, (24,))  # 3 pages
    tok0, _ = eng.prefill(0, prompt, temperature=0.0)
    task = eng.prefill_begin(1, prompt, temperature=0.0)
    assert task.shared_tokens == 23          # capped at n-1
    while not eng.prefill_step(task):
        pass
    assert task.chunks_run == 1, \
        "fully-cached prompt should admit in ONE 1-token chunk"
    assert task.first_token == tok0


def _greedy_stream(eng, slot, first_tok, n):
    """Decode ``n`` greedy tokens for ``slot`` alone (other lanes
    inactive — their writes are dropped in-program)."""
    S = eng.num_slots
    toks = [int(first_tok)]
    for _ in range(n):
        feed = [0] * S
        feed[slot] = toks[-1]
        active = [False] * S
        active[slot] = True
        nt, _ = eng.decode(feed, active, [0.0] * S, [0] * S, [1.0] * S)
        toks.append(int(nt[slot]))
    return toks


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_cow_mutating_one_sharer_never_perturbs_another():
    """Two requests share prefix pages (including the capped tail page,
    whose final-token write copy-on-writes at admission); each then
    decodes while the other's pages sit in the same pool.  Greedy
    decode is RNG-independent, so each stream must be IDENTICAL to a
    fresh single-request engine where nothing was ever shared."""
    m = _tiny_model()
    prompt = np.random.default_rng(17).integers(0, 512, (16,))  # 2 pages

    eng = _engine(m, num_slots=2, max_len=64, page_size=8, seed=5)
    tok0, _ = eng.prefill(0, prompt, temperature=0.0)
    tok1, _ = eng.prefill(1, prompt, temperature=0.0)   # shares + CoWs
    assert eng._alloc.refcount.max() == 2               # page 0 shared
    # slot 0 decodes first (appends into its private tail/new pages),
    # then slot 1 — if any shared byte was perturbed, slot 1 diverges
    s0 = _greedy_stream(eng, 0, tok0, 8)
    s1 = _greedy_stream(eng, 1, tok1, 8)

    ref0 = _engine(m, num_slots=2, max_len=64, page_size=8, seed=5)
    rtok0, _ = ref0.prefill(0, prompt, temperature=0.0)
    r0 = _greedy_stream(ref0, 0, rtok0, 8)
    ref1 = _engine(m, num_slots=2, max_len=64, page_size=8, seed=5)
    rtok1, _ = ref1.prefill(1, prompt, temperature=0.0)
    r1 = _greedy_stream(ref1, 1, rtok1, 8)

    assert s0 == r0, "sharer 0's stream perturbed by sharing"
    assert s1 == r1, \
        "slot 0's appends perturbed slot 1 through a shared page"


def test_shared_full_pages_stay_shared_through_decode():
    m = _tiny_model()
    eng = _engine(m, num_slots=2, max_len=64, page_size=8, seed=5)
    prompt = np.random.default_rng(19).integers(0, 512, (16,))
    eng.prefill(0, prompt, temperature=0.0)
    eng.prefill(1, prompt, temperature=0.0)
    al = eng._alloc
    shared_pid = int(al.table[1, 0])
    assert int(al.refcount[shared_pid]) == 2
    # decode appends land in each slot's PRIVATE tail (rows 16+ — page
    # 2): the shared full page is never written, so it never copies
    before = eng.kv_stats["tokens"]
    eng.decode([1, 2], [True, True], [0.0, 0.0], [0, 0], [1.0, 1.0])
    assert int(al.refcount[shared_pid]) == 2      # still shared, intact
    assert eng.kv_stats["tokens"] == before + 2


def test_cow_fires_when_append_targets_shared_page():
    """Force the CoW path directly: share a half-full tail page between
    two slots, then decode the sharer — its append lands IN the shared
    page and must copy first."""
    m = _tiny_model()
    eng = _engine(m, num_slots=2, max_len=64, page_size=8, seed=5)
    prompt = np.random.default_rng(23).integers(0, 512, (12,))
    eng.prefill(0, prompt, temperature=0.0)      # pages 0 (full), 1 (4 rows)
    al = eng._alloc
    # manually share slot 0's PARTIAL tail page into slot 1 (what a
    # tail-digest prefix hit does) and give slot 1 the same length
    al.share(1, 0, int(al.table[0, 0]))
    al.share(1, 1, int(al.table[0, 1]))
    eng._set_length(1, 12)
    pid_before = int(al.table[1, 1])
    assert al.needs_cow(1, 1) and al.needs_cow(0, 1)
    eng.decode([3, 3], [True, True], [0.0, 0.0], [0, 0], [1.0, 1.0])
    # the shared tail page was un-shared before either row-12 write:
    # the two slots now map DIFFERENT private pages (which slot kept
    # the original is an implementation detail of CoW order)
    assert int(al.table[0, 1]) != int(al.table[1, 1])
    assert int(al.refcount[al.table[0, 1]]) == 1
    assert int(al.refcount[al.table[1, 1]]) == 1
    assert int(al.refcount[pid_before]) == 1
    assert eng.decode_compile_count == 1


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

def test_chunked_prefill_matches_one_shot():
    m = _tiny_model()
    prompt = np.random.default_rng(29).integers(0, 512, (30,))
    ref = _full_last_logits(m, prompt)
    eng = _engine(m, num_slots=1, max_len=64, page_size=8,
                  prefill_chunk=8)
    task = eng.prefill_begin(0, prompt, temperature=0.0)
    steps = 0
    while not eng.prefill_step(task):
        steps += 1
    assert steps + 1 == -(-30 // 8)          # ceil(n/chunk) chunks total
    np.testing.assert_allclose(np.asarray(task.last_logits), ref,
                               rtol=2e-4, atol=2e-4)
    assert eng.prefill_compile_count == 1, \
        "chunked prefill must be ONE program"


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_chunked_prefill_interleaves_with_decode_tpot():
    """TPOT non-interference: while a long prompt admits chunk-by-chunk,
    the in-flight request KEEPS generating (one decode per scheduler
    iteration) — and the admission still produces correct greedy
    output."""
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    m = _tiny_model()
    eng = _engine(m, num_slots=2, max_len=128, page_size=8,
                  prefill_chunk=8)
    sched = ContinuousBatchingScheduler(eng)
    short = np.random.default_rng(31).integers(0, 512, (4,))
    long = np.random.default_rng(37).integers(0, 512, (60,))
    r_short = sched.submit(Request(prompt=short, max_new_tokens=20,
                                   temperature=0.0))
    sched.step()                              # admit + first decode
    assert sched.slots[0].generated, "short request must be decoding"
    r_long = sched.submit(Request(prompt=long, max_new_tokens=4,
                                  temperature=0.0))
    # 60 tokens / 8-chunk = 8 chunks: during those iterations the short
    # request must gain one token per step (no whole-prompt stall)
    gen_before = len(sched.slots[0].generated)
    iters = 0
    while sched.slots[1] is None or sched.slots[1].prefill_task is not None:
        sched.step()
        iters += 1
        assert iters < 50
    gen_after = len(sched.slots[0].generated)
    assert gen_after - gen_before >= iters - 1, \
        "chunked admission stalled the in-flight request's decode"
    res = sched.run()
    # greedy correctness of both under interleaving
    assert res[r_short].tokens.size == 20
    assert res[r_long].tokens.size == 4
    seq = list(long)
    for t in res[r_long].tokens:
        np.testing.assert_allclose(
            _full_last_logits(m, seq).argmax(), t)
        seq.append(int(t))
    assert eng.decode_compile_count == 1
    assert eng.prefill_compile_count == 1


# ---------------------------------------------------------------------------
# refcount-aware eviction
# ---------------------------------------------------------------------------

def test_eviction_prefers_max_unshared_pages():
    """Prefix-heavy workload: slots whose pages are mostly SHARED would
    free almost nothing — the victim must be the slot with the most
    unshared pages even when it was admitted first (not bare FIFO)."""
    from paddle_tpu.serving.scheduler import ContinuousBatchingScheduler
    m = _tiny_model()
    # pool is deliberately tight: 3 slots x 4 pages capacity but only
    # 8 physical pages
    eng = _engine(m, num_slots=3, max_len=32, page_size=8, num_pages=8,
                  prefill_chunk=8)
    sched = ContinuousBatchingScheduler(eng)
    rng = np.random.default_rng(41)
    shared_prompt = rng.integers(0, 512, (16,))   # 2 pages
    unique_prompt = rng.integers(0, 512, (24,))   # 3 pages, all private
    # slot 0: unique (oldest — bare FIFO would evict THIS one's sharers)
    eng.prefill(0, unique_prompt, temperature=0.0)
    # slots 1, 2: the same prompt — pages shared between them
    eng.prefill(1, shared_prompt, temperature=0.0)
    eng.prefill(2, shared_prompt, temperature=0.0)
    assert eng.unshared_pages(0) == 3
    # slot 1's page 0 is shared with slot 2; page 1 is private (capped
    # prefix), so unshared(1) == unshared(2) == 1
    assert eng.unshared_pages(1) == 1 and eng.unshared_pages(2) == 1
    # fake-occupy the scheduler so _evict_for_pages sees all three
    class _A:                      # minimal stand-in for _ActiveSlot
        def __init__(self, order):
            self.admit_order = order
            self.prefill_task = None
            self.generated = [1]
            self.submit_t = self.first_tok_t = self.last_t = 0.0
            self.decode_s = 0.0
            self.queue_wait = 0.0
            self.prefix_hit_tokens = 0
            import dataclasses as _d
            from paddle_tpu.serving.scheduler import Request
            self.req = _d.replace(Request(prompt=np.asarray([1]),
                                          max_new_tokens=1), rid=order)
    sched.slots = [_A(0), _A(1), _A(2)]
    assert sched._evict_for_pages(requester_idx=1)
    # victim must be slot 0 (3 unshared pages), NOT slot 2 (FIFO tie or
    # shared-heavy)
    assert sched.slots[0] is None, "eviction picked a shared-heavy slot"
    assert sched.slots[2] is not None


def test_scheduler_paged_cache_full_run():
    """End-to-end over a tight pool: everything completes, nothing
    hangs, decode still ONE program."""
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    m = _tiny_model()
    eng = _engine(m, num_slots=2, max_len=32, page_size=8, num_pages=6,
                  prefill_chunk=8)
    sched = ContinuousBatchingScheduler(eng)
    rng = np.random.default_rng(43)
    rids = [sched.submit(Request(prompt=rng.integers(0, 512, (n,)),
                                 max_new_tokens=10, temperature=0.0))
            for n in (8, 16, 8, 24)]
    res = sched.run()
    assert set(res) == set(rids)
    for r in res.values():
        assert r.tokens.size >= 1
    assert eng.decode_compile_count == 1
    assert eng.prefill_compile_count == 1


def test_decode_append_capped_at_max_len():
    """max_len NOT a multiple of page_size: the pool's tail page has
    rows past the engine's declared capacity.  A direct caller (no
    scheduler to retire the slot) keeping a full lane active must not
    use them — appends drop in-program and lengths (device AND the host
    mirror) clamp at max_len, matching the slotted layout's
    rows-past-max_len guard."""
    eng = _engine(_tiny_model(), num_slots=1, max_len=12, page_size=8,
                  num_pages=4)
    prompt = np.random.default_rng(5).integers(0, 512, (8,))
    tok, _ = eng.prefill(0, prompt, temperature=0.0)
    for _ in range(8):                  # 4 appends fit, 4 more must drop
        tok_arr, _ = eng.decode([int(tok)], [True], [0.0], [0], [1.0])
        tok = int(tok_arr[0])
    assert int(eng.slot_lengths()[0]) == 12
    assert int(np.asarray(eng.cache.lengths)[0]) == 12


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_model_level_paged_cache_respects_declared_max_len():
    """gen_paged_cache(max_len=12, page_size=8) allocates 16 rows of
    pool capacity; the declared budget rides the cache as static aux
    data, so the bare-cache decode path (``model(x, cache=...)`` — no
    engine to pass the cap) drops appends past 12 exactly like
    gen_cache's slotted guard: the tail page's dead rows stay zero and
    lengths clamp."""
    m = _tiny_model()
    cache = m.gen_paged_cache(1, max_len=12, page_size=8)
    assert cache.max_len == 12
    ids = np.random.default_rng(9).integers(0, 512, (1, 1)).astype("int32")
    for _ in range(16):
        _logit, cache = m(paddle.to_tensor(ids), cache=cache)
    assert int(np.asarray(cache.lengths)[0]) == 12
    assert cache.max_len == 12, "declared cap lost across finalize()"
    # positions 12..15 (page 1, local rows 4..7) must never be written
    assert not np.asarray(cache.k)[1, :, 4:].any()


def test_preemption_requeues_evicted_victim():
    """Page-pool-pressure eviction must not silently drop a request:
    the victim is requeued and recomputed (prompt + generated-so-far),
    so every submitted request still returns its FULL greedy completion
    — identical to an uncontended run — and nothing comes back empty."""
    from paddle_tpu import observability as obs
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    m = _tiny_model()
    rng = np.random.default_rng(71)
    prompts = [rng.integers(0, 512, (24,)) for _ in range(2)]

    def run_with(num_pages):
        eng = _engine(m, num_slots=2, max_len=48, page_size=8,
                      num_pages=num_pages, prefill_chunk=8)
        sched = ContinuousBatchingScheduler(eng)
        rids = [sched.submit(Request(prompt=p, max_new_tokens=8,
                                     temperature=0.0))
                for p in prompts]
        res = sched.run()
        assert eng.decode_compile_count <= 1
        return [res[r] for r in rids]

    before = obs.counter("serving.preemptions").value
    tight = run_with(num_pages=6)   # both need 5 pages; 6 forces evicts
    assert obs.counter("serving.preemptions").value > before, \
        "pool was not tight enough to exercise preemption"
    roomy = run_with(num_pages=12)
    for t, r in zip(tight, roomy):
        assert t.finish_reason == "length" and r.finish_reason == "length"
        assert t.tokens.size == r.tokens.size == 8
        np.testing.assert_array_equal(t.tokens, r.tokens)


def test_generate_seed_reproducible_across_prefix_cache():
    """generate(seed=s) must return identical SAMPLED tokens on the
    engine_for-cached engine even when the second call's admission
    prefix-hits (collapsing a 2-chunk prefill into one 1-token chunk):
    only the final chunk may consume a key from the threaded stream —
    a per-chunk draw would let prefix-cache state shift every later
    sample's key."""
    from paddle_tpu.serving import generate
    m = _tiny_model(seed=3)
    prompt = np.random.default_rng(83).integers(0, 512, (100,))
    a = generate(m, prompt, max_new_tokens=5, temperature=1.0, seed=0)
    b = generate(m, prompt, max_new_tokens=5, temperature=1.0, seed=0)
    np.testing.assert_array_equal(a[0], b[0])


def test_refresh_state_drops_stale_prefix_cache():
    """A prefix hit must never map pages whose K/V was computed under
    OLD parameters: after the params change, refresh_state() purges the
    hash cache, so re-admitting the same prompt recomputes from scratch
    and matches a fresh engine.  An UNCHANGED re-snapshot (what every
    cached-engine reuse does) keeps the cache — sharing survives."""
    import jax
    m = _tiny_model()
    eng = _engine(m, num_slots=1, max_len=64, page_size=8)
    prompt = np.random.default_rng(29).integers(0, 512, (16,))
    _tok, logits0 = eng.prefill(0, prompt, temperature=0.0)
    ref0 = np.asarray(logits0)
    eng.free_slot(0)

    # identical params: the retired pages stay hash-reachable
    eng.refresh_state()
    task = eng.prefill_begin(0, prompt, temperature=0.0)
    assert task.shared_tokens == 15
    while not eng.prefill_step(task):
        pass
    np.testing.assert_allclose(np.asarray(task.last_logits), ref0,
                               rtol=1e-5, atol=1e-5)
    eng.free_slot(0)

    # perturb the params: the cache is stale and must be dropped
    new_state = {k: (v + 0.01 if jax.numpy.issubdtype(v.dtype,
                                                      jax.numpy.floating)
                     else v)
                 for k, v in eng.state.items()}
    eng.refresh_state(new_state)
    task = eng.prefill_begin(0, prompt, temperature=0.0)
    assert task.shared_tokens == 0, "stale prefix pages served after " \
                                    "a parameter change"
    while not eng.prefill_step(task):
        pass
    # and the logits match a FRESH engine built on the new params
    fresh = _engine(m, num_slots=1, max_len=64, page_size=8)
    fresh.refresh_state(new_state)
    _tok, logits_fresh = fresh.prefill(0, prompt, temperature=0.0)
    np.testing.assert_allclose(np.asarray(task.last_logits),
                               np.asarray(logits_fresh),
                               rtol=1e-5, atol=1e-5)


def test_zero_token_eviction_reports_no_ttft():
    """A request evicted before producing ANY token (cache_full while
    still prefilling) reports ttft 0.0 and contributes NO sample to the
    serving.ttft_seconds histogram — a fabricated eviction-time TTFT
    would pollute the p50/p99 the bench reports."""
    from paddle_tpu import observability as obs
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    eng = _engine(_tiny_model(), num_slots=2, max_len=32, page_size=8,
                  prefill_chunk=8)
    sched = ContinuousBatchingScheduler(eng)
    rng = np.random.default_rng(11)
    rid = sched.submit(Request(prompt=rng.integers(0, 512, (24,)),
                               max_new_tokens=2, temperature=0.0))
    assert sched.admit() == 1
    before = obs.histogram("serving.ttft_seconds").count
    sched._finish(0, "cache_full")     # evicted mid-prefill: no token yet
    res = sched.finished[rid]
    assert res.tokens.size == 0 and res.ttft == 0.0
    assert obs.histogram("serving.ttft_seconds").count == before


def test_prefix_hit_reported_in_result():
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    m = _tiny_model()
    # ONE slot: r2 is admitted only after r1 retired, so its lookup sees
    # r1's registered pages — as free-but-cached entries (refcount 0,
    # still reachable by digest).  Concurrent admissions of the same
    # novel prompt do NOT share: lookup runs at admission, registration
    # at prefill completion, and admit() fills every free slot first.
    eng = _engine(m, num_slots=1, max_len=64, page_size=8)
    sched = ContinuousBatchingScheduler(eng)
    prompt = np.random.default_rng(47).integers(0, 512, (16,))
    r1 = sched.submit(Request(prompt=prompt, max_new_tokens=2,
                              temperature=0.0))
    r2 = sched.submit(Request(prompt=prompt, max_new_tokens=2,
                              temperature=0.0))
    res = sched.run()
    assert res[r1].prefix_hit_tokens == 0
    # both full pages hit (chained digests cover the whole prompt),
    # capped at n-1 so the final token reruns through the chunk program
    assert res[r2].prefix_hit_tokens == 15


# ---------------------------------------------------------------------------
# compile-once across everything + KV accounting
# ---------------------------------------------------------------------------

def test_compile_once_across_churn_prefix_hits_and_chunks():
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    m = _tiny_model()
    eng = _engine(m, num_slots=2, max_len=64, page_size=8,
                  prefill_chunk=8)
    sched = ContinuousBatchingScheduler(eng)
    rng = np.random.default_rng(53)
    shared = rng.integers(0, 512, (16,))
    for i in range(6):
        prompt = shared if i % 2 else rng.integers(0, 512, (5 + 7 * i,))
        sched.submit(Request(prompt=prompt, max_new_tokens=6,
                             temperature=float(i % 2) * 0.5,
                             top_k=(0, 7)[i % 2], top_p=(1.0, 0.8)[i % 2]))
    res = sched.run()
    assert len(res) == 6
    assert eng.decode_compile_count == 1, \
        "decode retraced across churn/prefix/chunks: %d programs" \
        % eng.decode_compile_count
    assert eng.prefill_compile_count == 1
    assert int(eng._cow._cache_size()) <= 1


def test_kv_bytes_accounting_scales_with_true_lengths():
    m = _tiny_model()
    eng = _engine(m, num_slots=2, max_len=64, page_size=8)
    eng.prefill(0, np.asarray([1, 2, 3], np.int32), temperature=0.0)
    eng.prefill(1, np.asarray([4, 5, 6, 7], np.int32), temperature=0.0)
    for t in range(4):
        eng.decode([1, 2], [True, True], [0.0, 0.0], [0, 0], [1.0, 1.0])
    b = eng.kv_bytes_per_token()
    assert b["paged"] > 0.0
    # short sequences: one page each vs the 64-row flat bound per slot
    assert b["paged"] < b["flat"] / 4, \
        "paged KV read bound did not scale with true lengths: %r" % b


def test_paged_decode_hlo_has_no_s64_compute():
    import re

    import jax
    from paddle_tpu.analysis import S64_COMPUTE_OPS
    from paddle_tpu.core.dtype import x64_scope
    m = _tiny_model()
    eng = _engine(m)
    with x64_scope(False):
        lowered = jax.jit(
            eng._decode_fn,
            donate_argnums=eng._decode_donate_argnums).lower(
            *eng.decode_trace_args())
    hlo = lowered.compile().as_text()
    assert "f64[" not in hlo
    for op in S64_COMPUTE_OPS:
        pat = re.compile(r"s64\[[0-9,]*\]\S* " + op + r"\(")
        assert not pat.search(hlo), "s64 %s leaked into paged decode" % op


def test_paged_programs_registered_for_audit():
    from paddle_tpu.analysis.trace.programs import builder_names
    assert "serving" in builder_names()
    # the builder registers the paged entries (cheap structural check —
    # the full lowering runs in the audit CI job)
    import inspect

    from paddle_tpu.analysis.trace import programs as P
    src = inspect.getsource(P._build_serving)
    for name in ("serving/decode_step", "serving/prefill_chunk",
                 "serving/cow_copy"):
        assert name in src
