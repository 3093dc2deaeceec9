"""Disaggregated prefill/decode serving (ISSUE 15).

The role-split contract these tests pin:

* **parity** — greedy output is BIT-IDENTICAL to the colocated engine
  across admission churn, prefix hits, preemption, speculative + int8
  composition, and both layer layouts: the chunk programs are the same
  programs, the transfer copies page bytes exactly, per-slot decode
  math is independent of batch composition;
* **compile-once per role** — prefill engine: chunk program +
  ``kv_export``; decode engine: decode (+ ``spec_verify``) +
  ``kv_import`` — each exactly one program under the strict watchdog;
* **failure discipline** — an injected ``SocketReset``/``TornFile`` at
  the ``serve.handoff`` faultpoint mid-transfer REQUEUES the request
  (recompute path) with pages freed refcount-exactly on BOTH pools,
  and both engines stay serviceable afterwards;
* **routing** — real prefill compute only ever runs on the prefill
  engine; a decode-pool full prefix hit admits decode-side in one
  1-token chunk, skipping prefill AND transfer;
* **observability** — the ``handoff`` span keeps the request tree
  connected, the ``serve.handoff`` beacon/faultpoint are declared, and
  the new mixes drive seeded-reproducible workloads.
"""
import json
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.robustness.faultpoints import (FaultPlan, SITES,
                                               SocketReset, TornFile,
                                               chaos)
from paddle_tpu.serving.disagg import DisaggScheduler
from paddle_tpu.serving.engine import DecodeEngine
from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                          Request)

VOCAB = 128


def _tiny_model(seed=0):
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


def _requests(n=6, seed=0, max_new=(3, 9), plen=(4, 40), eos=None):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, VOCAB, (int(rng.integers(
                        plen[0], plen[1])),)).astype(np.int32),
                    max_new_tokens=int(rng.integers(*max_new)),
                    temperature=0.0, eos_token_id=eos)
            for _ in range(n)]


def _pair(model, slots=3, pslots=2, max_len=64, page_size=8, pinned=True,
          **kw):
    """A (decode, prefill) engine pair — device-pinned onto two host
    devices when available (the production shape), meshless otherwise."""
    import jax
    devs = jax.devices()
    d0 = devs[0] if (pinned and len(devs) >= 2) else None
    d1 = devs[1] if (pinned and len(devs) >= 2) else None
    de = DecodeEngine(model, num_slots=slots, max_len=max_len, seed=0,
                      page_size=page_size, device=d0, **kw)
    pkw = {k: v for k, v in kw.items() if k not in ("spec_k",)}
    pe = DecodeEngine(model, num_slots=pslots, max_len=max_len, seed=0,
                      page_size=page_size, device=d1, **pkw)
    return de, pe


def _drive(sched, reqs):
    rids = [sched.submit(Request(prompt=r.prompt.copy(),
                                 max_new_tokens=r.max_new_tokens,
                                 temperature=r.temperature,
                                 eos_token_id=r.eos_token_id))
            for r in reqs]
    res = sched.run()
    return [(tuple(int(t) for t in res[r].tokens), res[r].finish_reason)
            for r in rids]


def _colocated(model, reqs, slots=3, max_len=64, page_size=8, **kw):
    eng = DecodeEngine(model, num_slots=slots, max_len=max_len, seed=0,
                       page_size=page_size, **kw)
    return _drive(ContinuousBatchingScheduler(eng), reqs)


# ---------------------------------------------------------------------------
# greedy bit-parity vs the colocated engine (the acceptance sweep)
# ---------------------------------------------------------------------------

def test_disagg_greedy_parity_with_admission_churn(model, monkeypatch):
    """6 requests through 3 decode / 2 prefill slots: admissions churn
    through both roles, every request hands off, and the output is
    bit-identical to the colocated engine — under the strict watchdog,
    with kv_export/kv_import each exactly one program."""
    monkeypatch.setenv("PADDLE_TPU_STRICT_COMPILE", "1")
    reqs = _requests()
    colo = _colocated(model, reqs)
    de, pe = _pair(model)
    sched = DisaggScheduler(de, pe)
    assert _drive(sched, reqs) == colo
    assert sched.handoffs_total > 0
    assert sched.handoff_bytes_total > 0
    dc = de.flight_state()["compile_counts"]
    pc = pe.flight_state()["compile_counts"]
    assert dc["decode"] == 1 and dc["kv_import"] == 1
    assert dc["prefill"] == 0 and dc["kv_export"] == 0
    assert pc["prefill"] == 1 and pc["kv_export"] == 1
    assert pc["decode"] == 0 and pc["kv_import"] == 0
    # every pool page returned (prefix-cached pages are refcount-0)
    assert de._alloc.pages_used() == 0
    assert pe._alloc.pages_used() == 0


def test_disagg_parity_meshless_same_device(model):
    """Without device pinning (one shared device, both engines
    meshless) the handoff passes device arrays through untouched and
    parity still holds — the single-device CI smoke shape."""
    reqs = _requests(4, seed=3)
    de, pe = _pair(model, pinned=False)
    sched = DisaggScheduler(de, pe)
    assert _drive(sched, reqs) == _colocated(model, reqs)
    assert sched.handoffs_total > 0


def test_disagg_parity_fresh_model_second_seed():
    m = _tiny_model()
    reqs = _requests(4, seed=1)
    de, pe = _pair(m)
    assert _drive(DisaggScheduler(de, pe), reqs) == _colocated(m, reqs)


@pytest.mark.parametrize("kw", [
    dict(spec_k=2),
    pytest.param(dict(kv_dtype="int8"), marks=pytest.mark.slow),
    pytest.param(dict(spec_k=2, kv_dtype="int8"),
                 marks=pytest.mark.slow),
], ids=["spec", "int8", "spec_int8"])
def test_disagg_parity_spec_int8_composition(model, monkeypatch, kw):
    """Speculative decode and the int8 pool compose with the role
    split: the transfer moves codes + scale rows byte-wise, the verify
    program stays one program, and greedy output is bit-identical to
    the equally-configured colocated engine."""
    monkeypatch.setenv("PADDLE_TPU_STRICT_COMPILE", "1")
    reqs = _requests(5, seed=2)
    colo = _colocated(model, reqs, **kw)
    de, pe = _pair(model, **kw)
    sched = DisaggScheduler(de, pe)
    assert _drive(sched, reqs) == colo
    if kw.get("spec_k"):
        assert de.flight_state()["compile_counts"]["verify"] == 1
    assert sched.handoffs_total > 0


def test_disagg_parity_via_host_staging(model, monkeypatch):
    """The host-staging transport (PADDLE_TPU_HANDOFF_HOST=1 — the
    disjoint-mesh fallback) round-trips every chunk through a spilled
    npz and still reproduces the colocated output bit-exactly."""
    monkeypatch.setenv("PADDLE_TPU_HANDOFF_HOST", "1")
    reqs = _requests(4, seed=4)
    de, pe = _pair(model)
    sched = DisaggScheduler(de, pe)
    assert sched.via_host
    assert _drive(sched, reqs) == _colocated(model, reqs)
    assert sched.handoffs_total > 0


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_disagg_via_host_staging_bf16_pool(model, monkeypatch):
    """The host-staging spill must round-trip ml_dtypes pools
    byte-exactly: npz saves bfloat16 as void '|V2' and a naive reload
    would be misread as a torn transport (requeue loop → cache_full).
    A bf16-pool disagg drive over the host transport must match the
    equally-configured colocated engine bit-for-bit."""
    import jax.numpy as jnp
    monkeypatch.setenv("PADDLE_TPU_HANDOFF_HOST", "1")
    reqs = _requests(3, seed=14)
    colo = _colocated(model, reqs, cache_dtype=jnp.bfloat16)
    de, pe = _pair(model, cache_dtype=jnp.bfloat16)
    sched = DisaggScheduler(de, pe)
    assert sched.via_host
    assert _drive(sched, reqs) == colo
    assert sched.handoffs_total > 0
    assert all(r[1] == "length" for r in _drive(sched, reqs[:1]))


def test_disagg_prefix_hit_skips_prefill_and_transfer(model):
    """A prompt whose pages the DECODE pool already holds (registered
    at handoff completion) admits decode-side in one 1-token chunk:
    same tokens, no new handoff, and the routing counters show exactly
    one decode-side chunk for exactly one decode-route admission."""
    de, pe = _pair(model)
    sched = DisaggScheduler(de, pe)
    # page-aligned prompt: decode appends land in a FRESH page, so the
    # registered prefix pages stay byte-stable for the second admission
    prompt = np.arange(24, dtype=np.int32) % VOCAB
    r1 = Request(prompt=prompt.copy(), max_new_tokens=4, temperature=0.0)
    first = _drive(sched, [r1])
    assert sched.handoffs_total == 1
    assert sched.decode_route_admissions == 0
    r2 = Request(prompt=prompt.copy(), max_new_tokens=4, temperature=0.0)
    second = _drive(sched, [r2])
    assert second == first
    assert sched.handoffs_total == 1          # no second transfer
    assert sched.decode_route_admissions == 1
    assert sched.decode_side_chunks == 1      # the 1-token hit chunk
    res = sched.finished[list(sched.finished)[-1]]
    assert res.prefix_hit_tokens > 0


def test_disagg_single_token_requests_never_hand_off(model):
    """max_new_tokens=1 retires on the prefill side — the decode pool
    never hears about it, and the result matches colocated."""
    reqs = _requests(3, seed=5, max_new=(1, 2))
    for r in reqs:
        r.max_new_tokens = 1
    de, pe = _pair(model)
    sched = DisaggScheduler(de, pe)
    assert _drive(sched, reqs) == _colocated(model, reqs)
    assert sched.handoffs_total == 0
    assert de._alloc.pages_used() == 0
    assert pe._alloc.pages_used() == 0


def test_disagg_preemption_under_decode_pool_pressure(model):
    """A decode pool too small for the offered load forces recompute
    preemption mid-run (possibly mid-handoff): completions stay
    bit-identical to the colocated engine driven at the same pressure
    and both pools drain refcount-exactly."""
    import jax
    reqs = _requests(5, seed=6, plen=(16, 40), max_new=(4, 8))
    devs = jax.devices()
    # tighten ONLY the decode pool: 12 pages << 3 slots * 8 max pages
    de2 = DecodeEngine(model, num_slots=3, max_len=64, seed=0,
                       page_size=8, num_pages=12,
                       device=devs[0] if len(devs) >= 2 else None)
    pe = DecodeEngine(model, num_slots=2, max_len=64, seed=0,
                      page_size=8,
                      device=devs[1] if len(devs) >= 2 else None)
    sched = DisaggScheduler(de2, pe)
    out = _drive(sched, reqs)
    roomy = _colocated(model, reqs)
    # finish reasons may differ (cache_full cap under extreme pressure)
    # but every request that completed normally matches bit-exactly
    for got, want in zip(out, roomy):
        if got[1] in ("eos", "length"):
            assert got == want
    assert de2._alloc.pages_used() == 0
    assert pe._alloc.pages_used() == 0


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_disagg_handoff_limit_backpressure(model):
    """handoff_limit=1 bounds the ready queue: prefill-complete slots
    park (pages held) until the queue drains, and everything still
    completes bit-identically."""
    reqs = _requests(6, seed=7)
    de, pe = _pair(model, slots=2, pslots=2)
    sched = DisaggScheduler(de, pe, handoff_limit=1)
    assert _drive(sched, reqs) == _colocated(model, reqs, slots=2)
    assert sched.handoff_depth == 0


def test_disagg_seeded_first_tokens_reproducible(model):
    """temperature>0 with a seed: the PREFILL-sampled first token per
    request reproduces run-to-run (admission order and the
    one-key-per-admission stream are deterministic).  Decode-side
    samples are reproducible only per-mode, not run-to-run: the
    decode step index at which a handed-off request joins depends on
    the non-blocking ``is_ready()`` poll (wall clock) — same caveat
    class as the overlapped loop's overshoot keys, documented in
    SERVING.md.  Greedy full-sequence parity is pinned above."""
    reqs = _requests(4, seed=8)
    for r in reqs:
        r.temperature = 0.9

    def run():
        de, pe = _pair(model)
        return [t[0][0] for t in _drive(DisaggScheduler(de, pe), reqs)]

    assert run() == run()


# ---------------------------------------------------------------------------
# serve.handoff chaos: torn transport mid-handoff
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("via_host,action", [
    (False, SocketReset), (True, TornFile)],
    ids=["device-reset", "host-torn"])
def test_chaos_mid_handoff_requeues_and_stays_serviceable(
        model, via_host, action):
    """An injected transport fault on a mid-handoff chunk requeues the
    request at the queue front (recompute), frees BOTH pools
    refcount-exactly, completes every request with full budgets, and
    leaves both engines serviceable."""
    reqs = _requests(3, seed=9, plen=(16, 40), max_new=(4, 5))
    de, pe = _pair(model, slots=2, pslots=2)
    sched = DisaggScheduler(de, pe, via_host=via_host)
    plan = FaultPlan().inject("serve.handoff", action(), at=2)
    with chaos(plan):
        out = _drive(sched, reqs)
    plan.assert_all_fired()
    assert all(len(t) == r.max_new_tokens and reason == "length"
               for (t, reason), r in zip(out, reqs))
    assert de._alloc.pages_used() == 0
    assert pe._alloc.pages_used() == 0
    # the aborted transfer never counted; the recompute's retry did
    assert sched.handoffs_total == len(reqs)
    # both engines stay serviceable
    again = _drive(sched, reqs[:1])
    assert len(again[0][0]) == reqs[0].max_new_tokens


def test_chaos_persistent_torn_transport_caps_at_cache_full(model):
    """A transport that tears EVERY chunk: each recompute round still
    emits one prefill-sampled token, so a SHORT request completes
    "length" without ever handing off, while a budget past the
    max_preemptions cap finishes "cache_full" instead of looping
    forever — the eviction-starvation discipline."""
    rng = np.random.default_rng(10)
    prompt = rng.integers(0, VOCAB, (24,)).astype(np.int32)
    de, pe = _pair(model, slots=2, pslots=2)
    sched = DisaggScheduler(de, pe)
    plan = FaultPlan().inject("serve.handoff", SocketReset(), every=1)
    with chaos(plan):
        long_out = _drive(sched, [Request(prompt=prompt.copy(),
                                          max_new_tokens=8,
                                          temperature=0.0)])
        short_out = _drive(sched, [Request(
            prompt=prompt[:16].copy(), max_new_tokens=3,
            temperature=0.0)])
    plan.assert_all_fired()
    # 1 admission + max_preemptions recomputes = 4 prefill-sampled
    # tokens, then the cap retires it
    assert long_out[0][1] == "cache_full"
    assert len(long_out[0][0]) == 1 + sched.max_preemptions
    assert short_out[0][1] == "length"
    assert len(short_out[0][0]) == 3
    assert sched.handoffs_total == 0
    assert de._alloc.pages_used() == 0
    assert pe._alloc.pages_used() == 0
    # serviceable after the plan is gone
    ok = _drive(sched, [Request(prompt=prompt.copy(), max_new_tokens=4,
                                temperature=0.0)])
    assert ok[0][1] == "length" and len(ok[0][0]) == 4


def test_handoff_advance_tolerates_mid_loop_retirement(model):
    """A chunk's page-pressure eviction (or cap retirement) can pick
    ANOTHER mid-handoff slot as its victim — `_preempt`/`_finish` pop
    it from `_handoffs` while `_handoff_advance` iterates a snapshot of
    the keys.  The loop must skip the vanished task, not KeyError (the
    scheduler thread dying would error-done every open stream)."""
    rng = np.random.default_rng(13)
    # handoff_pages=1: a 3-page prompt takes 3 chunks, so two handoffs
    # are genuinely concurrent mid-transfer
    de, pe = _pair(model, slots=3, pslots=2, handoff_pages=1)
    sched = DisaggScheduler(de, pe)
    for _ in range(2):
        sched.submit(Request(prompt=rng.integers(0, VOCAB, (24,)),
                             max_new_tokens=3, temperature=0.0))
    sched.admit()
    # the final chunk's token is POLLED, and XLA:CPU runs the chunk
    # asynchronously: poll against a deadline, not an iteration count
    deadline = time.monotonic() + 60.0
    while len(sched._handoffs) < 2 and time.monotonic() < deadline:
        sched.prefill_once()
        time.sleep(0.001)
    assert len(sched._handoffs) == 2, "handoffs never got concurrent"
    # simulate the re-entrant retirement: processing the FIRST task's
    # chunk preempts the SECOND mid-handoff slot (what _alloc_dst's
    # eviction fallback does under pool pressure)
    first, second = list(sched._handoffs)
    orig = sched._handoff_chunk
    fired = []

    def chunk_with_eviction(task):
        if task.dst_slot == first and not fired:
            fired.append(True)
            sched._preempt(second)
        orig(task)

    sched._handoff_chunk = chunk_with_eviction
    sched._handoff_advance()          # must not raise
    assert fired and second not in sched._handoffs
    sched._handoff_chunk = orig
    res = sched.run()                 # the preempted request recomputes
    assert len(res) == 2
    assert all(len(r.tokens) == 3 for r in res.values())
    assert de._alloc.pages_used() == 0
    assert pe._alloc.pages_used() == 0


def test_chaos_site_and_beacon_declared():
    from paddle_tpu.observability.liveness import BEACONS
    assert "serve.handoff" in SITES
    assert "serve.handoff" in BEACONS


# ---------------------------------------------------------------------------
# construction validation
# ---------------------------------------------------------------------------

def test_disagg_constructor_validation(model):
    de, pe = _pair(model)
    with pytest.raises(ValueError, match="TWO engines"):
        DisaggScheduler(de, de)
    with pytest.raises(ValueError, match="spec_k=0"):
        DisaggScheduler(de, DecodeEngine(model, num_slots=2, max_len=64,
                                         seed=0, page_size=8, spec_k=2))
    with pytest.raises(ValueError, match="geometry"):
        DisaggScheduler(de, DecodeEngine(model, num_slots=2, max_len=64,
                                         seed=0, page_size=16))
    slotted = DecodeEngine(model, num_slots=2, max_len=64, seed=0,
                           paged=False)
    with pytest.raises(ValueError, match="paged"):
        DisaggScheduler(slotted, pe)
    with pytest.raises(ValueError, match="handoff_limit"):
        DisaggScheduler(de, pe, handoff_limit=0)
    import jax
    if len(jax.devices()) >= 2:
        pinned_pe = DecodeEngine(model, num_slots=2, max_len=64, seed=0,
                                 page_size=8, device=jax.devices()[1])
        meshless_de = DecodeEngine(model, num_slots=2, max_len=64,
                                   seed=0, page_size=8)
        with pytest.raises(ValueError, match="mesh-placed"):
            DisaggScheduler(meshless_de, pinned_pe)


def test_engine_export_import_validation(model):
    de, _pe = _pair(model, pinned=False)
    with pytest.raises(ValueError, match="export_pages"):
        de.export_pages([])
    with pytest.raises(ValueError, match="export_pages"):
        de.export_pages(list(range(de.handoff_pages + 1)))
    slotted = DecodeEngine(model, num_slots=2, max_len=64, seed=0,
                           paged=False)
    with pytest.raises(RuntimeError, match="paged-engine"):
        slotted.export_pages([0])
    with pytest.raises(RuntimeError, match="paged-engine"):
        slotted.import_pages((None,) * 4, [0])


# ---------------------------------------------------------------------------
# observability: handoff span, metrics, audit registration
# ---------------------------------------------------------------------------

def test_handoff_span_keeps_request_tree_connected(model):
    """Each handed-off request's lane gains a ``handoff`` span, child
    of the request root — trace-report must still see one CONNECTED
    tree per request."""
    from paddle_tpu.observability.tracing import Tracer, build_report
    tr = Tracer()
    de, pe = _pair(model, tracer=tr)
    sched = DisaggScheduler(de, pe, tracer=tr)
    reqs = _requests(3, seed=11)
    _drive(sched, reqs)
    rep = build_report(tr.spans(), tr.instants())
    assert rep["totals"]["connected"]
    assert len(rep["requests"]) == 3
    spans = tr.spans()
    by_id = {s["span_id"]: s for s in spans}
    handoffs = [s for s in spans if s["name"] == "handoff"]
    assert len(handoffs) == 3
    for s in handoffs:
        assert by_id[s["parent_id"]]["name"] == "request"
        assert s["attrs"].get("bytes", 0) > 0


def test_handoff_metrics_fire(model):
    import paddle_tpu.observability as obs
    reg = obs.default_registry()
    reg.reset()
    de, pe = _pair(model)
    sched = DisaggScheduler(de, pe)
    _drive(sched, _requests(3, seed=12))
    assert obs.counter("serving.handoff_bytes").value == \
        sched.handoff_bytes_total > 0
    assert obs.histogram("serving.handoff_seconds").count > 0
    assert obs.gauge("serving.handoff_queue_depth").value == 0


def test_handoff_programs_registered_for_audit():
    # cheap structural check — the full lowering runs in the audit CI
    # job (same discipline as the paged-entry registration test)
    import inspect

    from paddle_tpu.analysis.trace import programs as P
    src = inspect.getsource(P._build_serving)
    for name in ("serving/kv_export", "serving/kv_import"):
        assert name in src


# ---------------------------------------------------------------------------
# loadgen: the new mixes + the interference drive
# ---------------------------------------------------------------------------

def test_new_mixes_shapes():
    from paddle_tpu.serving.loadgen import MIXES
    (plo, phi), (nlo, nhi) = MIXES["prefill_heavy"]
    assert plo <= phi and nlo <= nhi
    assert plo > nhi * 4        # prompts dominate: the interference mix
    (plo, phi), (nlo, nhi) = MIXES["decode_heavy"]
    assert plo <= phi and nlo <= nhi
    assert nlo > phi            # outputs dominate: streams stay live


def test_prefill_heavy_mix_seeded_reproducible(model):
    """Two seeded drives of the prefill_heavy mix through a live
    disaggregated front-end deliver the identical per-request token
    counts — the loadgen seeding contract on the new mix."""
    from paddle_tpu.serving.frontend import ServingFrontend
    from paddle_tpu.serving import loadgen
    de, pe = _pair(model, max_len=128, page_size=16)
    fe = ServingFrontend(de, prefill_engine=pe)
    host, port = fe.start()
    try:
        runs = [loadgen.run_load_sync(host, port, qps=50.0,
                                      n_requests=4, mix="prefill_heavy",
                                      seed=7, vocab=VOCAB)
                for _ in range(2)]
    finally:
        fe.stop()
    assert runs[0]["completed"] == runs[1]["completed"] == 4
    assert runs[0]["goodput_tokens"] == runs[1]["goodput_tokens"]


@pytest.mark.slow
def test_run_interference_wave_block_and_repeats(model):
    """The interference drive produces a well-formed wave block, and
    ``repeats=2`` pools the samples of two seeded cycles."""
    from paddle_tpu.serving.frontend import ServingFrontend
    from paddle_tpu.serving import loadgen
    de, pe = _pair(model, max_len=128, page_size=16, slots=4)
    fe = ServingFrontend(de, prefill_engine=pe)
    host, port = fe.start()
    try:
        s1 = loadgen.run_interference_sync(
            host, port, qps=30.0, n_requests=8, mix="decode_heavy",
            wave_n=2, wave_qps=20.0, seed=3, vocab=VOCAB)
        s2 = loadgen.run_interference_sync(
            host, port, qps=30.0, n_requests=8, mix="decode_heavy",
            wave_n=2, wave_qps=20.0, seed=3, vocab=VOCAB, repeats=2)
    finally:
        fe.stop()
    w1, w2 = s1["wave"], s2["wave"]
    assert w1["repeats"] == 1 and w2["repeats"] == 2
    assert w2["requests"] == 2 * w1["requests"]
    assert w2["quiet_gaps"] > w1["quiet_gaps"]
    for w in (w1, w2):
        assert w["quiet_tpot_p50_ms"] <= w["quiet_tpot_p99_ms"]
        assert w["mix"] == "prefill_heavy"


# ---------------------------------------------------------------------------
# front-end integration
# ---------------------------------------------------------------------------

def test_frontend_disagg_healthz_and_stream(model):
    """The HTTP surface over a role-split scheduler: healthz exposes
    handoff_depth, and a streamed generate completes."""
    from paddle_tpu.serving.frontend import ServingFrontend
    de, pe = _pair(model)
    fe = ServingFrontend(de, prefill_engine=pe)
    host, port = fe.start()
    try:
        assert isinstance(fe.scheduler, DisaggScheduler)
        h = json.loads(urllib.request.urlopen(
            "http://%s:%d/healthz" % (host, port), timeout=10).read())
        assert h["status"] == "ok" and "handoff_depth" in h
        body = json.dumps({"prompt": list(range(12)),
                           "max_new_tokens": 3, "temperature": 0.0,
                           "stream": False}).encode()
        req = urllib.request.Request(
            "http://%s:%d/v1/generate" % (host, port), data=body,
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req, timeout=30).read())
        assert len(out["tokens"]) == 3
    finally:
        fe.stop()
    assert fe.scheduler.handoffs_total == 1
