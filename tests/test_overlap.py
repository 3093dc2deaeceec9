"""Overlapped host/device decode loop (ISSUE 13).

The scheduler's default loop keeps ONE decode step in flight: iteration
t dispatches the compiled step threading iteration t-1's sampled tokens
on DEVICE, then blocks only on t-1's fetch — host bookkeeping for t-1
overlaps device compute for t.  These tests pin the reconciliation
contract:

* greedy output is BIT-IDENTICAL to the sync loop (``overlap=False``)
  across admission churn, EOS landing on an in-flight step, prefix
  hits, speculative decode, recompute preemption, and both layer
  layouts;
* one-step-stale decisions are reconciled by identity-based lane
  crediting — an overshoot token computed for a since-retired /
  preempted / cancelled slot is discarded, and the host length mirror
  stays exact;
* the overlapped loop opens NO second jit cache entry (the device-token
  threading and the host-token path hit the same compiled program —
  strict-watchdog-tested);
* ``cancel()`` frees the slot and its pages refcount-exactly;
* the host-gap accounting shows the structural win: the sync loop pays
  the consume->dispatch host window every step, the overlapped loop
  only true bubbles.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving.engine import DecodeEngine
from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                          Request)

VOCAB = None


def _tiny_model(seed=0):
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


def _drive(model, overlap, n_req=5, slots=2, max_new=8, eos=None,
           paged=True, spec=0, num_pages=None, prompt_len=8, seed=1,
           max_len=64, on_token=None, temperature=0.0):
    cfg = model.config
    eng = DecodeEngine(model, num_slots=slots, max_len=max_len, seed=0,
                       page_size=8, paged=paged, spec_k=spec,
                       num_pages=num_pages)
    sched = ContinuousBatchingScheduler(eng, overlap=overlap,
                                        on_token=on_token)
    rng = np.random.default_rng(seed)
    rids = [sched.submit(Request(
        prompt=rng.integers(0, cfg.vocab_size, (prompt_len,)),
        max_new_tokens=max_new, temperature=temperature,
        eos_token_id=eos)) for _ in range(n_req)]
    res = sched.run()
    out = [(tuple(int(t) for t in res[r].tokens), res[r].finish_reason)
           for r in rids]
    return out, eng, sched


# ---------------------------------------------------------------------------
# sync-vs-overlapped greedy bit-parity (the acceptance sweep)
# ---------------------------------------------------------------------------

@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "slotted"])
def test_greedy_bit_parity_with_admission_churn(model, paged):
    """5 requests through 2 slots: admissions land while a step is in
    flight (the freed lane's overshoot token must be discarded, the new
    occupant joins the NEXT dispatch with its host-known first token)."""
    sync, _, _ = _drive(model, overlap=False, paged=paged)
    over, eng, _ = _drive(model, overlap=True, paged=paged)
    assert sync == over
    assert eng.decode_compile_count == 1


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_eos_lands_on_inflight_step(model):
    """EOS discovered at consume time, AFTER the next step was already
    dispatched with the finished slot still active: the overshoot token
    is discarded and the sequences match the sync loop exactly."""
    base, _, _ = _drive(model, overlap=False, max_new=10)
    # a token every request emits mid-stream (greedy is deterministic)
    eos = base[0][0][2]
    sync, _, s_sync = _drive(model, overlap=False, max_new=10,
                             eos=int(eos))
    over, _, s_over = _drive(model, overlap=True, max_new=10,
                             eos=int(eos))
    assert sync == over
    assert any(r[1] == "eos" for r in sync)
    # the overlapped loop really ran overshoot iterations (stale
    # dispatches whose lane credit was discarded)
    assert s_over.decode_steps_total >= s_sync.decode_steps_total


def test_overlap_threading_keeps_one_program(model, monkeypatch):
    """The device-token threading and the host-token first dispatch hit
    the SAME jit cache entry; under the strict watchdog a second entry
    would raise at the offending step."""
    monkeypatch.setenv("PADDLE_TPU_STRICT_COMPILE", "1")
    over, eng, _ = _drive(model, overlap=True, n_req=6, max_new=6)
    assert eng.decode_compile_count == 1
    assert eng.prefill_compile_count == 1
    assert len(over) == 6


@pytest.mark.slow
def test_overlap_spec_greedy_parity(model):
    """Speculative verify under overlap: drafts are built from one-step-
    stale history (quality lever only) — greedy output must still be
    bit-identical, and fixed k keeps ONE verify program."""
    sync, _, _ = _drive(model, overlap=False, spec=3)
    over, eng, _ = _drive(model, overlap=True, spec=3)
    assert [t for t, _ in sync] == [t for t, _ in over]
    assert eng.verify_compile_count == 1
    # regression (slot-epoch guard): the overshoot verify step consumed
    # AFTER its lane was freed must not resurrect the zeroed length
    # mirror — a second scheduler on the SAME engine must admit cleanly
    assert int(eng.slot_lengths().sum()) == 0
    sched2 = ContinuousBatchingScheduler(eng, overlap=True)
    rng = np.random.default_rng(7)
    r = sched2.submit(Request(
        prompt=rng.integers(0, model.config.vocab_size, (8,)),
        max_new_tokens=4, temperature=0.0))
    assert sched2.run()[r].tokens.size == 4


@pytest.mark.slow
def test_overlap_spec_eos_truncation_parity(model):
    base, _, _ = _drive(model, overlap=False, spec=3, max_new=10)
    eos = base[0][0][1]
    sync, _, _ = _drive(model, overlap=False, spec=3, max_new=10,
                        eos=int(eos))
    over, _, _ = _drive(model, overlap=True, spec=3, max_new=10,
                        eos=int(eos))
    assert sync == over


@pytest.mark.slow
def test_overlap_preemption_of_undrained_slot(model):
    """Tight page pool: a prefill chunk's page demand preempts a victim
    while a decode step is in flight.  The loop drains the step BEFORE
    evicting (a parked token list must never lag the device), the
    victim recomputes, and greedy output matches the sync loop."""
    from paddle_tpu import observability as obs
    kw = dict(n_req=3, slots=2, max_new=8, prompt_len=20,
              num_pages=7, max_len=48)
    sync, _, _ = _drive(model, overlap=False, **kw)
    pre = obs.counter("serving.preemptions").value
    over, eng, sched = _drive(model, overlap=True, **kw)
    assert sync == over
    assert eng.decode_compile_count == 1
    # pool pressure actually bit (otherwise this test proves nothing)
    assert obs.counter("serving.preemptions").value > pre
    assert all(a is None for a in sched.slots)
    assert eng._alloc.pages_used() == 0


def test_overlap_host_mirror_exact_after_drain(model):
    """After run() completes (final in-flight step consumed), the
    engine's host length mirror is all-zero and the pool is empty: no
    overshoot append leaked a page or a length."""
    _, eng, sched = _drive(model, overlap=True, n_req=5)
    assert sched._inflight is None
    assert eng._alloc.pages_used() == 0
    assert int(eng.slot_lengths().sum()) == 0


@pytest.mark.slow
def test_host_gap_reduced(model):
    """The structural claim: the sync loop pays host time between fetch
    and the next dispatch on every step; the overlapped loop dispatches
    BEFORE consuming, so its gap collapses to true bubbles."""
    _, _, s_sync = _drive(model, overlap=False, n_req=4, max_new=10)
    _, _, s_over = _drive(model, overlap=True, n_req=4, max_new=10)
    assert s_sync.decode_steps_total > 0
    assert s_sync.host_gap_seconds > 0.0
    assert (s_over.host_gap_seconds / max(s_over.decode_steps_total, 1)
            <= s_sync.host_gap_seconds
            / max(s_sync.decode_steps_total, 1))


def test_on_token_stream_matches_results(model):
    """The streaming hook delivers exactly the tokens the results carry,
    in order, for every request (overlapped loop)."""
    got = {}
    out, _, _ = _drive(
        model, overlap=True, n_req=4,
        on_token=lambda rid, toks: got.setdefault(rid, []).extend(toks))
    for rid, (tokens, _reason) in enumerate(out):
        assert tuple(got[rid]) == tokens


@pytest.mark.slow
def test_overlap_seeded_sampling_reproducible(model):
    """temperature>0 under overlap: the loop is deterministic, so the
    same seed reproduces (the cross-mode sequences may differ — only
    greedy is mode-invariant, documented)."""
    a, _, _ = _drive(model, overlap=True, temperature=0.8)
    b, _, _ = _drive(model, overlap=True, temperature=0.8)
    assert a == b


# ---------------------------------------------------------------------------
# cancel() (the front-end's disconnect path)
# ---------------------------------------------------------------------------

def test_cancel_active_slot_frees_pages(model):
    cfg = model.config
    eng = DecodeEngine(model, num_slots=2, max_len=64, seed=0,
                       page_size=8)
    sched = ContinuousBatchingScheduler(eng, overlap=True)
    rng = np.random.default_rng(0)
    r0 = sched.submit(Request(prompt=rng.integers(0, cfg.vocab_size,
                                                  (8,)),
                              max_new_tokens=30, temperature=0.0))
    r1 = sched.submit(Request(prompt=rng.integers(0, cfg.vocab_size,
                                                  (8,)),
                              max_new_tokens=4, temperature=0.0))
    for _ in range(4):
        sched.step()
    used_before = eng._alloc.pages_used()
    assert used_before > 0
    assert sched.cancel(r0) is True
    res = sched.run()
    assert res[r0].finish_reason == "cancelled"
    assert res[r0].tokens.size >= 1          # partial tokens ride along
    assert res[r1].finish_reason == "length"
    assert res[r1].tokens.size == 4          # survivor unaffected
    assert eng._alloc.pages_used() == 0      # refcount-exact, no leak
    assert sched.cancel(r0) is False         # already finished


def test_cancel_waiting_request(model):
    cfg = model.config
    eng = DecodeEngine(model, num_slots=1, max_len=64, seed=0,
                       page_size=8)
    sched = ContinuousBatchingScheduler(eng, overlap=True)
    rng = np.random.default_rng(0)
    r0 = sched.submit(Request(prompt=rng.integers(0, cfg.vocab_size,
                                                  (8,)),
                              max_new_tokens=4, temperature=0.0))
    r1 = sched.submit(Request(prompt=rng.integers(0, cfg.vocab_size,
                                                  (8,)),
                              max_new_tokens=4, temperature=0.0))
    sched.step()                              # r0 admitted, r1 waiting
    assert sched.cancel(r1) is True
    res = sched.run()
    assert res[r1].finish_reason == "cancelled"
    assert res[r1].tokens.size == 0
    assert res[r0].finish_reason == "length"
    assert sched.cancel(999) is False
