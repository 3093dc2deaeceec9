"""Tiered KV cache: host-RAM page offload + cluster prefix index (ISSUE 17).

The tier contract these tests pin:

* **parity** — greedy output after a spill -> device-evict -> host-fetch
  -> resume round-trip is BIT-IDENTICAL to a cold tier-off run, across
  both layer layouts and the int8/speculative composition: the tier
  changes where the KV rows come from, never what gets generated;
* **full prefix hit** — a repeat-prompt admission that misses the
  device cache but hits the host tier re-admits with exactly ONE
  prefill chunk (the final 1-token chunk), ``kv_host_hits`` counting
  the pages that landed;
* **compile-once** — the kv_export/kv_import programs stay one program
  each under the strict watchdog no matter how many spills and fetches
  interleave with decode churn;
* **non-blocking fetch** — decode keeps dispatching (tokens keep
  landing) while a fetch is in flight: the fetch advances one phase
  per scheduler iteration, never stalling a decode dispatch;
* **failure discipline** — TornFile/BitFlip at the ``serve.kv_tier``
  faultpoint aborts the fetch, frees pages refcount-exactly, dumps the
  flight recorder, and degrades to recompute — degraded latency, never
  a wrong token;
* **LRU honesty** — the host tier refuses entries over budget, evicts
  oldest-first, and its byte accounting matches what it holds;
* **cluster index** — two publishers round-trip their digest sets
  through one TCPStore master; withdrawn digests disappear.
"""
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import flight
from paddle_tpu.robustness.faultpoints import (BitFlip, FaultPlan, SITES,
                                               TornFile, chaos)
from paddle_tpu.serving.engine import DecodeEngine
from paddle_tpu.serving.kv_tier import (ClusterPrefixIndex, HostPageTier,
                                        fetch_index)
from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                          Request)

VOCAB = 128
BUDGET = 16 << 20


def _tiny_model(seed=0):
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


def _engine(model, tier=True, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_len", 128)
    kw.setdefault("page_size", 16)
    # kv_host_bytes=0 pins the tier OFF regardless of the env knob
    return DecodeEngine(model, seed=0,
                        kv_host_bytes=BUDGET if tier else 0, **kw)


def _prompts(n=4, seed=0, plen=(20, 48)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (int(rng.integers(*plen)),))
            .astype(np.int32) for _ in range(n)]


def _drive(eng, prompts, max_new=6):
    sched = ContinuousBatchingScheduler(eng)
    rids = [sched.submit(Request(prompt=p.copy(), max_new_tokens=max_new,
                                 temperature=0.0))
            for p in prompts]
    res = sched.run()
    return [tuple(int(t) for t in res[r].tokens) for r in rids], sched


# ---------------------------------------------------------------------------
# HostPageTier units (host-side, no jax)
# ---------------------------------------------------------------------------

def _arrays(nbytes):
    return {"k": np.zeros(nbytes, np.uint8)}


def test_host_tier_lru_budget_honesty():
    tier = HostPageTier(budget_bytes=1000)
    assert tier.enabled and len(tier) == 0 and tier.bytes_used() == 0
    assert tier.put("a", _arrays(400))
    assert tier.put("b", _arrays(400))
    assert tier.bytes_used() == 800 and len(tier) == 2
    # the third entry evicts the OLDEST (a), not the budget
    assert tier.put("c", _arrays(400))
    assert "a" not in tier and "b" in tier and "c" in tier
    assert tier.bytes_used() == 800
    # a get() touches LRU order: b becomes hottest, d evicts c
    assert tier.get("b") is not None
    assert tier.put("d", _arrays(400))
    assert "c" not in tier and "b" in tier
    # an entry bigger than the whole budget is REFUSED, nothing evicted
    before = tier.digests()
    assert not tier.put("huge", _arrays(2000))
    assert tier.digests() == before
    # discard + clear keep the byte ledger exact
    tier.discard("b")
    assert tier.bytes_used() == 400
    st = tier.state()
    assert st["spilled"] == 4 and st["lru_evicted"] == 2
    assert st["bytes"] == 400 and st["budget_bytes"] == 1000
    tier.clear()
    assert tier.bytes_used() == 0 and len(tier) == 0
    # budget 0 = disabled: put refuses, get misses
    off = HostPageTier(budget_bytes=0)
    assert not off.enabled
    assert not off.put("a", _arrays(8))
    assert off.get("a") is None


# ---------------------------------------------------------------------------
# spill -> evict -> host-fetch -> resume bit-parity (the acceptance sweep)
# ---------------------------------------------------------------------------

def test_spill_fetch_greedy_parity(monkeypatch):
    """Wave 1 populates the device prefix cache; spill_cached_pages
    pushes every cached page to host RAM and evicts it device-side;
    wave 2 re-admits the same prompts THROUGH the host tier — greedy
    output bit-identical across both waves and vs a tier-off engine,
    under the strict watchdog."""
    monkeypatch.setenv("PADDLE_TPU_STRICT_COMPILE", "1")
    m = _tiny_model()
    prompts = _prompts(4, seed=1)
    baseline, _ = _drive(_engine(m, tier=False), prompts)

    eng = _engine(m)
    hits = obs.counter("serving.kv_host_hits")
    wave1, _ = _drive(eng, prompts)
    assert wave1 == baseline
    spilled = eng.spill_cached_pages()
    assert spilled > 0 and eng.kv_host_bytes_used() > 0
    h0 = hits.value
    wave2, _ = _drive(eng, prompts)
    assert wave2 == baseline
    assert hits.value > h0
    assert eng._alloc.pages_used() == 0
    cc = eng.flight_state()["compile_counts"]
    assert cc["kv_export"] == 1 and cc["kv_import"] == 1


@pytest.mark.slow  # composed-lever sweeps run in the CI serving job
@pytest.mark.parametrize("kw", [
    dict(spec_k=2),
    dict(spec_k=2, kv_dtype="int8"),
], ids=["spec", "spec_int8"])
def test_spill_fetch_parity_spec_int8_composition(model, monkeypatch, kw):
    """The int8 pool (codes + scale rows) and speculative decode
    compose with the tier: spilled rows round-trip byte-wise and the
    host-fetch wave stays bit-identical."""
    monkeypatch.setenv("PADDLE_TPU_STRICT_COMPILE", "1")
    prompts = _prompts(3, seed=2)
    baseline, _ = _drive(_engine(model, tier=False, **kw), prompts)
    eng = _engine(model, **kw)
    wave1, _ = _drive(eng, prompts)
    assert wave1 == baseline
    assert eng.spill_cached_pages() > 0
    wave2, _ = _drive(eng, prompts)
    assert wave2 == baseline
    assert eng._alloc.pages_used() == 0


def test_repeat_admission_is_full_prefix_hit(model):
    """The acceptance line: a repeat-prompt admission that misses the
    device cache but hits the host tier runs exactly ONE prefill chunk
    — the final 1-token chunk — with kv_host_hits counting the landed
    pages and the fetch histogram one observation."""
    prompt = _prompts(1, seed=3, plen=(40, 41))[0]        # 40 tokens
    eng = _engine(model)
    chunks = obs.histogram("serving.prefill_chunk_seconds")
    hits = obs.counter("serving.kv_host_hits")
    fetch_s = obs.histogram("serving.kv_tier_fetch_seconds")
    wave1, _ = _drive(eng, [prompt])
    assert eng.spill_cached_pages() > 0
    c0, h0, f0 = chunks.count, hits.value, fetch_s.count
    wave2, _ = _drive(eng, [prompt])
    assert wave2 == wave1
    assert chunks.count - c0 == 1          # ONLY the final 1-token chunk
    assert hits.value - h0 > 0
    assert fetch_s.count - f0 == 1
    assert obs.gauge("serving.kv_host_bytes").value == \
        eng.kv_host_bytes_used()


def test_fetch_interleaves_with_decode(model):
    """A fetch in flight never blocks a decode dispatch: while request
    B's pages stream back from the host tier, request A (already in a
    slot) keeps generating — the fetch spans multiple scheduler
    iterations and A's token count grows across them."""
    # 96 tokens = 6 full pages = multiple fetch chunks (handoff_pages
    # bounds a chunk), so the fetch must span several iterations
    pb = _prompts(1, seed=4, plen=(96, 97))[0]
    pa = _prompts(1, seed=5, plen=(24, 25))[0]
    eng = _engine(model)
    wave1, _ = _drive(eng, [pb])
    assert eng.spill_cached_pages() > 0

    sched = ContinuousBatchingScheduler(eng)
    ra = sched.submit(Request(prompt=pa.copy(), max_new_tokens=24,
                              temperature=0.0))
    rb = sched.submit(Request(prompt=pb.copy(), max_new_tokens=6,
                              temperature=0.0))
    gen_during_fetch = []
    while sched.has_work():
        sched.step()
        if rb in sched._fetches:
            a = next((s for s in sched.slots
                      if s is not None and s.req.rid == ra), None)
            gen_during_fetch.append(0 if a is None else len(a.generated))
    # the fetch really was in flight across iterations, and decode
    # progressed during that window
    results = sched.finished
    assert len(gen_during_fetch) >= 2
    assert gen_during_fetch[-1] > gen_during_fetch[0]
    assert tuple(int(t) for t in results[rb].tokens) == wave1[0]
    assert len(results[ra].tokens) == 24
    assert eng._alloc.pages_used() == 0


def test_compile_once_under_churn_and_fetches(model, monkeypatch):
    """Three waves with spills between them: admissions churn, pages
    spill, fetches interleave — kv_export/kv_import each stay exactly
    one program (the strict watchdog raises mid-drain otherwise)."""
    monkeypatch.setenv("PADDLE_TPU_STRICT_COMPILE", "1")
    eng = _engine(model)
    for seed in (6, 6, 6):
        _drive(eng, _prompts(4, seed=seed))
        eng.spill_cached_pages()
    cc = eng.flight_state()["compile_counts"]
    assert cc["kv_export"] == 1 and cc["kv_import"] == 1
    assert cc["decode"] == 1


# ---------------------------------------------------------------------------
# failure discipline: torn host-tier reads degrade to recompute
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("action", [TornFile, BitFlip],
                         ids=["torn", "bitflip"])
def test_chaos_torn_fetch_degrades_to_recompute(model, action, tmp_path):
    """An injected TornFile/BitFlip at the ``serve.kv_tier`` site tears
    the fetch's staging read-back: the fetch aborts, the torn digests
    leave the tier, pages free refcount-exactly, the flight recorder
    dumps, and the request completes by RECOMPUTE with bit-identical
    greedy output — degraded latency, never a wrong token."""
    prompt = _prompts(1, seed=7, plen=(40, 41))[0]
    eng = _engine(model)
    hits = obs.counter("serving.kv_host_hits")
    wave1, _ = _drive(eng, [prompt])
    assert eng.spill_cached_pages() > 0
    rec = flight.enable(dir=str(tmp_path))
    h0 = hits.value
    try:
        plan = FaultPlan().inject("serve.kv_tier", action(), at=0)
        with chaos(plan):
            wave2, _ = _drive(eng, [prompt])
        plan.assert_all_fired()
    finally:
        flight.disable()
    assert wave2 == wave1                      # recompute, never wrong
    assert hits.value == h0                    # a torn fetch counts NO hit
    assert eng._alloc.pages_used() == 0        # freed refcount-exactly
    assert rec.dumps, "no flight dump on fetch abort"
    dump = json.loads(open(rec.dumps[-1]).read())
    assert dump["trigger"]["kind"] == "kv_tier_abort"
    assert any(ev.get("kind") == "kv_tier_abort" for ev in dump["ring"])
    # serviceable afterwards (and the device cache re-registered the
    # recomputed pages, so this admission is a plain device prefix hit)
    wave3, _ = _drive(eng, [prompt])
    assert wave3 == wave1


def test_chaos_persistent_tear_still_completes(model):
    """A tear on EVERY roundtrip: each abort discards the staged
    digests, so the retry plan strictly shrinks and every request
    still completes correct by recompute — no livelock."""
    prompts = _prompts(2, seed=8)
    eng = _engine(model)
    wave1, _ = _drive(eng, prompts)
    assert eng.spill_cached_pages() > 0
    plan = FaultPlan().inject("serve.kv_tier", TornFile(), every=1)
    with chaos(plan):
        wave2, _ = _drive(eng, prompts)
    plan.assert_all_fired()
    assert wave2 == wave1
    assert eng._alloc.pages_used() == 0


def test_import_dispatch_tear_releases_pages_and_recomputes(model):
    """A raise out of ``import_pages`` — the fetch's phase-3 device
    scatter, AFTER the transport staging already verified clean — must
    release the freshly-allocated destination pages refcount-exactly
    and degrade the fetch to recompute (tpu-flow TPU701 found this
    path leaking: the pages were allocated, import raised, and nothing
    compensated)."""
    prompt = _prompts(1, seed=21, plen=(40, 41))[0]
    eng = _engine(model)
    wave1, _ = _drive(eng, [prompt])
    assert eng.spill_cached_pages() > 0
    calls = {"n": 0}

    def torn(bufs, pids):
        calls["n"] += 1
        raise RuntimeError("injected import tear")

    eng.import_pages = torn
    wave2, _ = _drive(eng, [prompt])
    assert calls["n"] >= 1, "fetch never reached the import phase"
    assert wave2 == wave1                      # recompute, never wrong
    assert eng._alloc.pages_used() == 0        # NO stranded dst pages
    # serviceable afterwards with the real import restored
    del eng.import_pages
    wave3, _ = _drive(eng, [prompt])
    assert wave3 == wave1


def test_cow_dispatch_tear_releases_fresh_page(model):
    """A raise out of the COW copy dispatch must release the freshly
    allocated private page before re-raising (tpu-flow TPU701 found
    ``new_pid`` held across the raising ``_cow`` call)."""
    eng = _engine(model)
    _drive(eng, _prompts(1, seed=22))
    used0 = eng._alloc.pages_used()

    def boom(*a, **k):
        raise RuntimeError("injected cow tear")

    eng._cow = boom
    with pytest.raises(RuntimeError, match="injected cow tear"):
        eng._cow_page(0, 0)
    assert eng._alloc.pages_used() == used0    # fresh page released


def test_chaos_site_and_beacon_declared():
    from paddle_tpu.observability.liveness import BEACONS
    assert "serve.kv_tier" in SITES
    assert "serve.kv_tier" in BEACONS


# ---------------------------------------------------------------------------
# tier off / engine state / observability plumbing
# ---------------------------------------------------------------------------

def test_tier_off_is_inert(model):
    eng = _engine(model, tier=False)
    assert eng._host_tier is None
    assert eng.kv_host_bytes_used() == 0
    assert eng.host_fetch_plan(np.arange(40, dtype=np.int32)) == []
    with pytest.raises(RuntimeError, match="host tier"):
        eng.spill_cached_pages()
    assert "kv_host" not in eng.flight_state()
    # the off engine still serves — the tier is strictly additive
    out, _ = _drive(eng, _prompts(2, seed=9))
    assert all(len(t) == 6 for t in out)


def test_flight_state_and_ledger_carry_host_tier(model):
    eng = _engine(model)
    _drive(eng, _prompts(2, seed=10))
    assert eng.spill_cached_pages() > 0
    st = eng.flight_state()["kv_host"]
    assert st["entries"] > 0 and st["bytes"] == eng.kv_host_bytes_used()
    assert st["budget_bytes"] == BUDGET
    from paddle_tpu.observability import hbm
    assert hbm.ledger_state()["kv_host_bytes"] >= st["bytes"]
    assert obs.counter("serving.kv_host_spilled_pages").value > 0


def test_refresh_state_clears_stale_tier(model):
    """Changed parameters must clear the HOST tier too: spilled rows
    were computed under the old weights, and a host hit would splice
    stale cache exactly like the device-hash hit refresh prevents."""
    eng = _engine(model)
    _drive(eng, _prompts(2, seed=11))
    assert eng.spill_cached_pages() > 0
    assert eng.kv_host_bytes_used() > 0
    other = _tiny_model(seed=99)
    eng.refresh_state(other.functional_state())
    assert eng.kv_host_bytes_used() == 0
    assert obs.gauge("serving.kv_host_bytes").value == 0


def test_kv_tier_span_keeps_request_tree_connected(model):
    """The fetch's ``kv_tier`` span is a child of the request root —
    trace-report still sees one CONNECTED tree per request."""
    from paddle_tpu.observability.tracing import Tracer, build_report
    prompt = _prompts(1, seed=12, plen=(40, 41))[0]
    tr = Tracer()
    eng = _engine(model, tracer=tr)
    sched = ContinuousBatchingScheduler(eng, tracer=tr)
    sched.submit(Request(prompt=prompt.copy(), max_new_tokens=4,
                         temperature=0.0))
    sched.run()
    assert eng.spill_cached_pages() > 0
    sched2 = ContinuousBatchingScheduler(eng, tracer=tr)
    sched2.submit(Request(prompt=prompt.copy(), max_new_tokens=4,
                          temperature=0.0))
    sched2.run()
    rep = build_report(tr.spans(), tr.instants())
    assert rep["totals"]["connected"]
    spans = tr.spans()
    by_id = {s["span_id"]: s for s in spans}
    kvt = [s for s in spans if s["name"] == "kv_tier"]
    assert len(kvt) == 1
    assert by_id[kvt[0]["parent_id"]]["name"] == "request"
    assert kvt[0]["attrs"].get("pages", 0) > 0


# ---------------------------------------------------------------------------
# cluster prefix index (TCPStore round-trip)
# ---------------------------------------------------------------------------

def test_cluster_index_roundtrip_two_hosts():
    """Two publishers (one per 'host') round-trip their digest sets
    through ONE TCPStore master; withdrawn digests disappear on the
    next publish; a host that never published is simply absent."""
    from paddle_tpu.distributed.store import TCPStore
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    i0 = ClusterPrefixIndex(TCPStore("127.0.0.1", master.port), host=0)
    i1 = ClusterPrefixIndex(TCPStore("127.0.0.1", master.port), host=1)
    i0.offer([b"\x01" * 8, b"\x02" * 8])
    i1.offer([b"\x03" * 8])
    i0.publish_once()
    i1.publish_once()
    idx = fetch_index(TCPStore("127.0.0.1", master.port), 3)
    assert set(idx) == {0, 1}                  # host 2 never published
    assert idx[0] == {(b"\x01" * 8).hex(), (b"\x02" * 8).hex()}
    assert idx[1] == {(b"\x03" * 8).hex()}
    i0.withdraw([b"\x01" * 8])
    i0.publish_once()
    idx = fetch_index(TCPStore("127.0.0.1", master.port), 2)
    assert idx[0] == {(b"\x02" * 8).hex()}


def test_cluster_index_publisher_thread():
    from paddle_tpu.distributed.store import TCPStore
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    idx = ClusterPrefixIndex(TCPStore("127.0.0.1", master.port), host=4,
                             interval=0.02)
    idx.offer([b"\xaa" * 8])
    idx.start()
    deadline = time.time() + 5.0
    while idx.published < 2 and time.time() < deadline:
        time.sleep(0.01)
    idx.stop()                       # also publishes the exit snapshot
    assert idx.published >= 2
    got = fetch_index(TCPStore("127.0.0.1", master.port), 5)
    assert got[4] == {(b"\xaa" * 8).hex()}


def test_engine_attach_cluster_index_offers_and_withdraws(model):
    """The engine wiring: prefill registrations and spills offer their
    digests; a parameter refresh withdraws everything."""
    from paddle_tpu.distributed.store import TCPStore
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    eng = _engine(model)
    eng.attach_cluster_index(TCPStore("127.0.0.1", master.port), host=0,
                             start=False)
    _drive(eng, _prompts(2, seed=13))
    eng._kv_index.publish_once()
    idx = fetch_index(TCPStore("127.0.0.1", master.port), 1)
    assert idx.get(0), "prefill registrations published no digests"
    eng.spill_cached_pages()
    eng.refresh_state(_tiny_model(seed=7).functional_state())
    eng._kv_index.publish_once()
    idx = fetch_index(TCPStore("127.0.0.1", master.port), 1)
    assert idx.get(0, set()) == set()


# ---------------------------------------------------------------------------
# eviction withdraw: store I/O never under a tier lock
# ---------------------------------------------------------------------------

def test_evict_hook_fires_outside_lock_and_is_best_effort():
    """LRU eviction invokes ``evict_hook`` with the evicted digests
    AFTER the tier lock is released, and a raising hook never fails
    the spill that triggered it."""
    tier = HostPageTier(budget_bytes=1000)
    seen = []

    def hook(digests):
        assert not tier._lock.locked(), "hook ran under the tier lock"
        seen.append(list(digests))
        raise RuntimeError("dead index")

    tier.evict_hook = hook
    assert tier.put("a", _arrays(400))
    assert tier.put("b", _arrays(400))
    assert tier.put("c", _arrays(400))         # evicts a; hook raises
    assert seen == [["a"]]
    assert "a" not in tier and "c" in tier     # spill still landed


def test_attach_cluster_index_wires_evict_hook(model):
    from paddle_tpu.distributed.store import TCPStore
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    eng = _engine(model)
    eng.attach_cluster_index(TCPStore("127.0.0.1", master.port), host=0,
                             start=False)
    assert eng._host_tier.evict_hook == eng._kv_index.withdraw


class _WedgedStore:
    """TCPStore proxy whose ``set`` blocks until released — models a
    wedged master mid-publish."""

    def __init__(self, inner):
        self._inner = inner
        self.entered = threading.Event()
        self.release = threading.Event()

    def set(self, key, value):
        self.entered.set()
        self.release.wait(10.0)
        self._inner.set(key, value)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_eviction_withdraw_survives_wedged_store():
    """The regression this PR's lock-discipline fix pins: with the
    publisher thread WEDGED inside ``store.set``, an over-budget
    ``put()`` (eviction -> hook -> withdraw) must complete promptly —
    withdraw only mutates the digest set under the index's own lock,
    and the tier calls the hook after releasing its lock, so a dead
    store can never wedge a spill.  Once the store recovers, the next
    publish advertises the post-withdraw truth."""
    from paddle_tpu.distributed.store import TCPStore
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    wedged = _WedgedStore(TCPStore("127.0.0.1", master.port))
    idx = ClusterPrefixIndex(wedged, host=0, interval=0.01)
    tier = HostPageTier(budget_bytes=1000)
    tier.evict_hook = idx.withdraw
    d1, d2, d3 = b"\x01" * 8, b"\x02" * 8, b"\x03" * 8
    assert tier.put(d1, _arrays(400)) and tier.put(d2, _arrays(400))
    idx.offer([d1, d2])
    idx.start()
    try:
        assert wedged.entered.wait(5.0), "publisher never reached set()"
        t0 = time.time()
        assert tier.put(d3, _arrays(400))      # evicts d1 -> withdraw
        assert time.time() - t0 < 2.0, "eviction blocked on the store"
        assert d1 not in tier
    finally:
        wedged.release.set()
        idx.stop()                             # publishes exit snapshot
    got = fetch_index(TCPStore("127.0.0.1", master.port), 1)
    assert got[0] == {d2.hex()}                # d1 withdrawn, d2 kept
