"""The unified-telemetry suite (ISSUE 6): metrics registry semantics,
catalog coverage (ops_schema-style), the no-op fast path, the never-traced
guard, the recompile watchdog (quiet + failure paths), exporters
(Prometheus / JSONL / chrome-trace marks), and the CLI."""
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import (CATALOG, NOOP_COUNTER, NOOP_GAUGE,
                                      NOOP_HISTOGRAM, Registry, watchdog)
from paddle_tpu.observability import exporters, registry as reg_mod


# ---------------------------------------------------------------------------
# registry core
# ---------------------------------------------------------------------------

def test_counter_gauge_basics_and_labels():
    reg = Registry(catalog=None)
    c = reg.counter("events", labels=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    assert c.labels(kind="a").value == 3.0
    assert c.labels(kind="b").value == 1.0
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)          # counters are monotonic
    with pytest.raises(ValueError):
        c.labels(wrong="a")                 # undeclared label key
    g = reg.gauge("depth")
    g.set(4)
    g.dec()
    assert g.value == 3.0


def test_histogram_percentiles_within_bucket_resolution():
    reg = Registry(catalog=None)
    h = reg.histogram("lat")
    rng = np.random.default_rng(0)
    vals = rng.uniform(1e-3, 1.0, size=2000)
    for v in vals:
        h.observe(float(v))
    for q in (0.5, 0.95, 0.99):
        exact = float(np.quantile(vals, q))
        est = h.percentile(q)
        # log-spaced buckets at 12/decade => ~21% max relative error
        assert abs(est - exact) / exact < 0.25, (q, est, exact)
    assert h.count == 2000
    assert abs(h.sum - float(vals.sum())) < 1e-6
    # readout never leaves the observed range (open-ended edge buckets)
    assert min(vals) <= h.percentile(0.0) <= h.percentile(1.0) <= max(vals)


def test_histogram_empty_and_extremes():
    reg = Registry(catalog=None)
    h = reg.histogram("x")
    assert h.percentile(0.5) == 0.0
    h.observe(0.0)            # below the first bound -> bucket 0
    h.observe(1e15)           # beyond the last bound -> overflow bucket
    assert h.count == 2
    assert h.percentile(1.0) == 1e15


def test_reset_zeroes_in_place_and_keeps_handles_live():
    """reset() must NOT drop the metric objects: components fetch handles
    once at construction (scheduler, watchdog), so a reset that cleared
    the dict would orphan every live handle — recordings after a
    bench-style warmup reset would silently vanish from snapshots."""
    reg = Registry(catalog=None)
    c = reg.counter("events", labels=("kind",))
    h = reg.histogram("lat")
    g = reg.gauge("depth")
    c.labels(kind="a").inc(3)
    h.observe(0.5)
    g.set(7)
    reg.reset()
    # values zeroed ...
    assert c.labels(kind="a").value == 0.0
    assert h.count == 0 and h.percentile(0.5) == 0.0
    assert g.value == 0.0
    # ... but the SAME objects keep recording and stay visible
    assert reg.counter("events", labels=("kind",)) is c
    c.labels(kind="a").inc()
    h.observe(0.25)
    snap = reg.snapshot()
    assert snap["events"]["series"][0]["value"] == 1.0
    assert snap["lat"]["series"][0]["count"] == 1


def test_disabled_fetch_still_validates_catalog():
    """Catalog strictness holds in metrics-off deployments too: fetches
    happen at construction (not the hot path), so a typo'd name should
    fail regardless of PADDLE_TPU_METRICS."""
    reg = obs.default_registry()
    assert reg.enabled, "suite assumes metrics on"
    reg.disable()
    try:
        with pytest.raises(ValueError, match="not declared"):
            reg.counter("definitely.not.declared")
        assert reg.counter("serving.finished_requests") is NOOP_COUNTER
    finally:
        reg.enable()


def test_registry_thread_safety_under_contention():
    reg = Registry(catalog=None)
    c = reg.counter("n")
    h = reg.histogram("h")

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000.0
    assert h.count == 8000


# ---------------------------------------------------------------------------
# catalog (ops_schema-style surface check)
# ---------------------------------------------------------------------------

def test_default_registry_rejects_undeclared_names():
    with pytest.raises(ValueError, match="not declared"):
        obs.counter("definitely.not.declared")
    with pytest.raises(ValueError, match="declared as a"):
        obs.gauge("serving.ttft_seconds")   # declared as histogram
    with pytest.raises(ValueError, match="labels"):
        obs.counter("serving.finished_requests", ("nope",))


def test_catalog_entries_are_well_formed():
    assert CATALOG, "catalog must not be empty"
    for name, spec in CATALOG.items():
        assert spec["type"] in ("counter", "gauge", "histogram"), name
        assert isinstance(spec["help"], str) and spec["help"], name
        assert isinstance(spec["labels"], tuple), name


@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_catalog_coverage_is_two_way(monkeypatch, tmp_path):
    """THE catalog ratchet (ISSUE 11 satellite): exercise every
    instrumented subsystem, then assert BOTH directions —

    (a) emission ⊆ catalog: everything recorded is declared;
    (b) catalog ⊆ emission: every declared metric fired in THIS test —
        a dead catalog entry (instrumentation deleted, or declared but
        never wired) fails loudly instead of rotting as dashboard
        documentation for a metric that no longer exists.

    Adding a catalog entry therefore requires adding its driver below —
    that is the ratchet, not an inconvenience."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.engine import DecodeEngine
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    from paddle_tpu.robustness import retry
    from paddle_tpu.robustness.faultpoints import (FaultPlan, ForceFoundInf,
                                                   SocketReset, chaos,
                                                   declare)
    from paddle_tpu.kernels import autotune as at
    from paddle_tpu.observability import hbm

    reg = obs.default_registry()
    assert reg.enabled, "suite assumes metrics on (PADDLE_TPU_METRICS)"

    paddle.seed(0)
    cfg = GPTConfig.tiny()
    cfg.hidden_dropout_prob = cfg.attention_dropout_prob = 0.0
    model = GPTForCausalLM(cfg)
    rng = np.random.default_rng(0)

    # -- serving A: the slotted layout (bucketed prefill hits) -------------
    slotted = DecodeEngine(model, num_slots=2, max_len=64, seed=0,
                           paged=False)
    sched = ContinuousBatchingScheduler(slotted)
    for _ in range(3):
        sched.submit(Request(prompt=rng.integers(0, cfg.vocab_size, (8,)),
                             max_new_tokens=3, temperature=0.0))
    sched.run()

    # -- serving B: paged + speculative + int8 + opt-in quant-error, on
    # a prefix-sharing workload (second admission of the shared prompt
    # lands after the first retires -> prefix hit), then a direct
    # double-prefill of one prompt (tail-page share -> CoW at admission)
    monkeypatch.setenv("PADDLE_TPU_METRICS_KV_QUANT_ERROR", "1")
    paged = DecodeEngine(model, num_slots=2, max_len=64, seed=0,
                         page_size=8, spec_k=2, kv_dtype="int8")
    monkeypatch.delenv("PADDLE_TPU_METRICS_KV_QUANT_ERROR")
    shared = rng.integers(0, cfg.vocab_size, (12,))
    sched2 = ContinuousBatchingScheduler(paged)
    sched2.submit(Request(prompt=shared, max_new_tokens=3,
                          temperature=0.0))
    sched2.run()
    sched3 = ContinuousBatchingScheduler(paged)
    sched3.submit(Request(prompt=shared, max_new_tokens=3,
                          temperature=0.0))
    sched3.run()
    paged.reset()
    paged.prefill(0, shared, temperature=0.0)
    paged.prefill(1, shared, temperature=0.0)   # shares + CoWs the tail

    # -- serving C: recompute preemption under page-pool pressure ----------
    tight = DecodeEngine(model, num_slots=2, max_len=48, seed=0,
                         page_size=8, num_pages=6, prefill_chunk=8)
    sched4 = ContinuousBatchingScheduler(tight)
    for _ in range(2):
        sched4.submit(Request(prompt=rng.integers(0, cfg.vocab_size, (24,)),
                              max_new_tokens=8, temperature=0.0))
    sched4.run()

    # -- serving D: tensor-parallel sharded decode (ISSUE 12) — drives the
    # tp_degree gauge past 1 and, via the opt-in, the per-step
    # collective-bytes counter priced from the compiled sharded program;
    # its collectives run as the rings (mp.overlap_chunks, one increment
    # an island traced)
    monkeypatch.setenv("PADDLE_TPU_METRICS_COLLECTIVES", "1")
    tp_eng = DecodeEngine(model, num_slots=2, max_len=32, seed=0,
                          page_size=8, tp=2, overlap_comm=True)
    monkeypatch.delenv("PADDLE_TPU_METRICS_COLLECTIVES")
    tok, _ = tp_eng.prefill(0, rng.integers(0, cfg.vocab_size, (6,)),
                            temperature=0.0)
    tp_eng.decode([tok, 0], [True, False], [0.0, 0.0], [0, 0],
                  [1.0, 1.0])

    # -- serving D2: disaggregated prefill/decode (ISSUE 15) — one real
    # role-split drive (prefill engine -> KV page handoff -> decode
    # engine) fires handoff bytes/seconds and the queue-depth gauge
    from paddle_tpu.serving.disagg import DisaggScheduler
    dis_de = DecodeEngine(model, num_slots=2, max_len=64, seed=0,
                          page_size=8)
    dis_pe = DecodeEngine(model, num_slots=2, max_len=64, seed=0,
                          page_size=8)
    dsched = DisaggScheduler(dis_de, dis_pe)
    dsched.submit(Request(prompt=rng.integers(0, cfg.vocab_size, (10,)),
                          max_new_tokens=3, temperature=0.0))
    dsched.run()
    assert dsched.handoffs_total >= 1

    # -- serving E: the async front-end (ISSUE 13) — one shed (429 +
    # shed_total) then one real streamed completion over HTTP (200,
    # open_streams, goodput_tokens) through the live asyncio server
    import json as _json
    import socket as _socket

    from paddle_tpu.serving.frontend import ServingFrontend
    paged.reset()
    fe = ServingFrontend(paged, queue_limit=0)
    fe.start()
    try:
        def _post(payload):
            s = _socket.create_connection((fe.host, fe.port), timeout=60)
            body = _json.dumps(payload).encode()
            s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: r\r\n"
                      b"Content-Length: %d\r\n\r\n" % len(body) + body)
            buf = b""
            while True:
                b = s.recv(65536)
                if not b:
                    break
                buf += b
            s.close()
            return buf
        raw = _post({"prompt": [1, 2, 3], "max_new_tokens": 2})
        assert b"429" in raw.split(b"\r\n")[0]     # shed over the bound
        fe.queue_limit = 8
        raw = _post({"prompt": [1, 2, 3], "max_new_tokens": 2,
                     "temperature": 0.0})
        assert b'"done": true' in raw              # streamed completion
    finally:
        fe.stop()

    # -- serving F: replicated fleet (ISSUE 19) — two replicas behind the
    # router, one killed mid-drive at the serve.replica site so every
    # fleet metric fires for real: routed{reason} on admission,
    # failovers on the crash requeue, replicas_healthy on the shrink
    import threading as _threading

    from paddle_tpu.robustness.faultpoints import HardExit
    from paddle_tpu.serving.router import Router
    fleet = [DecodeEngine(model, num_slots=2, max_len=64, seed=0,
                          page_size=8) for _ in range(2)]
    router = Router(fleet, probe_interval=None, respawn_delay=30.0)
    fin = {"n": 0}
    fleet_done = _threading.Event()

    def _fleet_finish(res):
        fin["n"] += 1
        if fin["n"] == 3:
            fleet_done.set()
    router.on_finish = _fleet_finish
    router.start()
    try:
        plan = FaultPlan(seed=0).inject("serve.replica", HardExit(), at=4)
        with chaos(plan):
            for _ in range(3):
                router.submit(Request(
                    prompt=rng.integers(0, cfg.vocab_size, (8,)),
                    max_new_tokens=4, temperature=0.0))
            assert fleet_done.wait(60), "fleet drive did not finish"
        plan.assert_all_fired()
        assert obs.counter("router.failovers").value >= 1
    finally:
        router.stop()

    # -- training: TrainStep (+ opt-in grad norm) and the hapi fit loop ----
    from paddle_tpu import hapi, nn
    from paddle_tpu.jit import TrainStep
    monkeypatch.setenv("PADDLE_TPU_METRICS_GRAD_NORM", "1")
    net = nn.Sequential(nn.Linear(4, 4))
    opt = paddle.optimizer.AdamW(parameters=net.parameters(),
                                 learning_rate=1e-3)
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(), opt)
    monkeypatch.delenv("PADDLE_TPU_METRICS_GRAD_NORM")
    x = jnp.ones((2, 4), jnp.float32)
    step(x, x)
    net2 = nn.Linear(8, 8)
    m = hapi.Model(net2)
    m.prepare(optimizer=paddle.optimizer.AdamW(
        parameters=net2.parameters(), learning_rate=1e-3),
        loss=lambda out, y: ((out - y) ** 2).mean())
    xb = jnp.ones((4, 8), jnp.float32)          # 2-D: train.tokens fires
    m.fit([(xb, xb)], epochs=1, verbose=0)

    # -- amp: a skipped fp16 step via the declared ForceFoundInf action ----
    scaler = paddle.amp.GradScaler(enable=True)
    with chaos(FaultPlan(seed=0).inject("amp.found_inf", ForceFoundInf(),
                                        at=0)):
        scaler.step(opt)
    assert scaler.last_step_skipped

    # -- divergence sentinel: one real rewind ------------------------------
    from paddle_tpu.robustness.sentinel import (DivergenceSentinel,
                                                DivergenceWarning)

    class _Stub:
        def __init__(self):
            self.state = {"w": 0.0}

        def state_dict(self):
            return dict(self.state)

        def set_state_dict(self, sd):
            self.state = dict(sd)

    sentinel = DivergenceSentinel(_Stub(), snapshot_every=1,
                                  max_snapshots=2, min_history=1)
    sentinel.observe(0, 1.0)
    sentinel.observe(1, 1.0)
    with pytest.warns(DivergenceWarning):
        sentinel.observe(2, float("nan"))

    # -- checkpoint: save + restore (also sets the restore transient) ------
    from paddle_tpu.incubate.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": np.ones((16,), np.float32)}, wait=True)
    mgr.close()
    CheckpointManager(str(tmp_path)).restore()

    # -- robustness: one retried transient + one injected fault ------------
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 2:
            raise ConnectionResetError("transient")
        return "ok"

    retry.retry_call(flaky, tries=3, sleep=lambda d: None)
    declare("test.obs_site", "observability coverage probe")
    with chaos(FaultPlan(seed=0).inject("test.obs_site", SocketReset(),
                                        at=0)):
        from paddle_tpu.robustness.faultpoints import faultpoint
        with pytest.raises(ConnectionResetError):
            faultpoint("test.obs_site")

    # -- autotune: resolve miss, one real timed tune, then the memoised
    # winner resolves as a HIT (both cache counters must fire)
    # (a family of the test's own: two candidates of one jnp function)
    x = jnp.ones((8, 64), jnp.float32)
    at.register_family(
        "_test_obs", lambda key: [{"variant": "base", "config": {"axis": a}}
                                  for a in (0, 1)],
        lambda cand, key: lambda: jax.block_until_ready(
            jnp.sum(x, axis=cand["config"]["axis"])))
    try:
        at.resolve("_test_obs", {"n": 8})
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_SAMPLES", "1")
        at.tune("_test_obs", {"n": 8}, persist=False)
        at.resolve("_test_obs", {"n": 8})
    finally:
        at._FAMILIES.pop("_test_obs", None)

    # -- flash kernels: one traced causal call counts its score elements,
    # its backward the residency the shape chose (flash.bwd_calls{path}) ---
    from paddle_tpu.kernels.flash_attention_pallas import \
        flash_attention_bshd_native
    qkv = jnp.zeros((1, 128, 2, 64), jnp.float32)
    jax.grad(lambda x: jnp.sum(flash_attention_bshd_native(
        x, x, x, causal=True, interpret=True)))(qkv)

    # -- hybrid blocks: one traced Mamba-2 scan and one traced expert
    # layer count themselves (ssm.scan_calls, ssm.conv_calls, moe.calls,
    # moe.rows) --------------------------------------------------------------
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)
    hybrid = NemotronHForCausalLM(NemotronHConfig.tiny(
        hybrid_override_pattern="ME"))
    hybrid(paddle.Tensor(jnp.zeros((1, 16), jnp.int32)))

    # -- a traced gated delta rule counts itself (linear_attn.scan_calls) --
    from paddle_tpu.nn.functional import linear_attn
    tokens = jnp.zeros((1, 16, 1, 16), jnp.float32)
    linear_attn.gated_delta_rule_raw(tokens, tokens, tokens,
                                     tokens[..., 0], tokens[..., 0], 16)

    # -- HBM ledger: one armed sample prices live arrays + KV pools --------
    hbm.enable()
    try:
        hbm.sample("ratchet")
    finally:
        hbm.disable()

    # -- liveness watchdog + cluster straggler view (ISSUE 14) -------------
    import time as _time

    from paddle_tpu.observability import aggregate as agg
    from paddle_tpu.observability import liveness as lv
    lv_mon = lv.enable(start=False)
    try:
        lv.declare_beacon("test.ratchet_stall", "ratchet driver")
        monkeypatch.setenv(
            "PADDLE_TPU_LIVENESS_DEADLINE_TEST_RATCHET_STALL", "0.0")
        with lv.beacon("test.ratchet_stall"):
            _time.sleep(0.005)
            assert lv_mon.check_now()       # liveness.stalls{beacon=}
    finally:
        lv.disable()

    def _host_doc(host, p50):
        return {"format": "paddle_tpu-telemetry-v1", "host": host,
                "pid": 1, "wall_ts": _time.time(), "beacons": {},
                "step_times": {"train.step_seconds": {
                    "count": 8, "sum": p50 * 8, "p50": p50,
                    "p95": p50, "p99": p50}},
                "stalls": {}, "metrics": {}}

    merged = agg.merge_docs({0: _host_doc(0, 0.1), 1: _host_doc(1, 0.4)},
                            2)              # liveness.straggler{host=}
    assert merged["stragglers"] == [1]

    snap = reg.snapshot()
    undeclared = set(snap) - set(CATALOG)
    assert not undeclared, "runtime metrics missing from catalog: %s" % (
        sorted(undeclared),)
    missing = sorted(set(CATALOG) - set(snap))
    assert not missing, (
        "catalog-declared metrics never emitted by this test: %s — either "
        "the instrumentation is dead (remove the catalog entry) or it is "
        "not wired (add a driver above)" % (missing,))
    # spot checks that the interesting paths really ran (not just the
    # metric objects existing): counters with observed activity
    for name in ("serving.prefix_hit_pages", "serving.cow_copies",
                 "serving.preemptions", "serving.spec_proposed_tokens",
                 "serving.collective_bytes", "liveness.stalls",
                 "liveness.straggler",
                 "train.amp_skipped_steps", "train.divergence_rollbacks"):
        total = sum(s.get("value", s.get("count", 0))
                    for s in snap[name]["series"])
        assert total > 0, "%s fired no samples" % name


# ---------------------------------------------------------------------------
# disabled => no-op fast path, no per-token host allocation
# ---------------------------------------------------------------------------

def test_disabled_registry_hands_out_noop_singletons():
    reg = Registry(catalog=None, enabled=False)
    assert reg.counter("a") is NOOP_COUNTER
    assert reg.gauge("b") is NOOP_GAUGE
    assert reg.histogram("c") is NOOP_HISTOGRAM
    # and the noops are inert under every method
    NOOP_COUNTER.inc()
    NOOP_COUNTER.labels(anything="x").inc(5)
    NOOP_HISTOGRAM.observe(1.0)
    assert NOOP_COUNTER.value == 0.0
    assert NOOP_HISTOGRAM.count == 0


def test_disabled_metrics_scheduler_hot_loop_is_noop():
    """Acceptance: registry disabled => the instrumented decode loop holds
    the shared no-op singletons by IDENTITY (no allocation, no recording
    on the per-token path) and live handles stop recording too."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.engine import DecodeEngine
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)

    reg = obs.default_registry()
    live = reg.histogram("serving.ttft_seconds")
    before = live.count
    reg.disable()
    try:
        cfg = GPTConfig.tiny()
        cfg.hidden_dropout_prob = cfg.attention_dropout_prob = 0.0
        engine = DecodeEngine(GPTForCausalLM(cfg), num_slots=2, max_len=64,
                              seed=0)
        sched = ContinuousBatchingScheduler(engine)
        assert sched._m_ttft is NOOP_HISTOGRAM
        assert sched._m_tokens is NOOP_COUNTER
        assert sched._m_decode_step is NOOP_HISTOGRAM
        assert sched._m_occupancy is NOOP_GAUGE
        rng = np.random.default_rng(0)
        sched.submit(Request(prompt=rng.integers(0, cfg.vocab_size, (8,)),
                             max_new_tokens=3, temperature=0.0))
        sched.run()
        # a pre-disable live handle records nothing while disabled
        live.observe(1.0)
        assert live.count == before
    finally:
        reg.enable()


# ---------------------------------------------------------------------------
# never traced
# ---------------------------------------------------------------------------

def test_registry_rejects_traced_values():
    reg = Registry(catalog=None)
    h = reg.histogram("h")
    c = reg.counter("c")

    def bad_hist(x):
        h.observe(x)
        return x

    def bad_counter(x):
        c.inc(x)
        return x

    with pytest.raises(RuntimeError, match="host-side only"):
        jax.jit(bad_hist)(jnp.ones(()))
    with pytest.raises(RuntimeError, match="host-side only"):
        jax.jit(bad_counter)(jnp.ones(()))


def test_observability_package_never_imported_by_traced_kernels():
    """Lint-style guard: the Pallas kernel modules (whose bodies run under
    tracing) must not import the registry at all."""
    import pathlib
    kdir = pathlib.Path(__file__).resolve().parent.parent / "paddle_tpu" \
        / "kernels"
    for f in kdir.glob("*_pallas.py"):
        assert "observability" not in f.read_text(), \
            "%s must stay registry-free (kernel bodies are traced)" % f.name


# ---------------------------------------------------------------------------
# recompile watchdog
# ---------------------------------------------------------------------------

def test_watchdog_quiet_path_decode_compiles_once_across_slot_churn():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.engine import DecodeEngine
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)

    cfg = GPTConfig.tiny()
    cfg.hidden_dropout_prob = cfg.attention_dropout_prob = 0.0
    engine = DecodeEngine(GPTForCausalLM(cfg), num_slots=2, max_len=64,
                          seed=0)
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(1)
    # more requests than slots + mixed lengths/budgets => admissions,
    # evictions, re-admissions — real slot churn
    for i in range(6):
        sched.submit(Request(
            prompt=rng.integers(0, cfg.vocab_size, (4 + 3 * (i % 3),)),
            max_new_tokens=2 + (i % 3), temperature=0.0))
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", watchdog.RecompileWarning)
        results = sched.run()
    assert len(results) == 6
    assert engine.decode_compile_count == 1
    # default engine is paged since ISSUE 7: ONE chunked-prefill program
    # regardless of prompt length (slotted engines bound it by their
    # power-of-two bucket count instead)
    if engine.paged:
        assert engine.prefill_compile_count == 1
    else:
        assert engine.prefill_compile_count <= len(engine.buckets)


def test_watchdog_failure_path_shape_unstable_entry():
    f = watchdog.watch("test.unstable", jax.jit(lambda x: x * 2),
                       expected=1)
    f(jnp.ones((2,)))
    # quiet while within budget
    assert f.compile_count == 1
    with pytest.warns(watchdog.RecompileWarning):
        f(jnp.ones((3,)))                 # second program: warn
    assert f.compile_count == 2
    os.environ["PADDLE_TPU_STRICT_COMPILE"] = "1"
    try:
        with pytest.raises(watchdog.RecompileError,
                           match="compile-once violation"):
            f(jnp.ones((4,)))             # third program: strict raise
    finally:
        del os.environ["PADDLE_TPU_STRICT_COMPILE"]


def test_watchdog_counts_flow_into_registry_and_report():
    before = watchdog.compile_counts().get("test.counted", 0)
    c = obs.counter("compile.count", ("entry",)).labels(
        entry="test.counted")
    v0 = c.value
    f = watchdog.watch("test.counted", jax.jit(lambda x: x + 1))
    f(jnp.ones((2,)))
    f(jnp.ones((2,)))    # same shape: no new program
    f(jnp.ones((5,)))    # new program (no budget set: counted, no warning)
    assert watchdog.compile_counts()["test.counted"] == before + 2
    assert c.value == v0 + 2


def test_watchdog_resync_after_registry_reset():
    """Registry.reset() zeroes the compile.count shadow; resync_counter()
    must bring it back to the watchdog's ground truth (the cache sizes) so
    Prometheus/JSONL exports agree with compile_counts() — the bench's
    post-warmup reset path."""
    f = watchdog.watch("test.resync", jax.jit(lambda x: x + 1))
    f(jnp.ones((2,)))
    f(jnp.ones((3,)))    # two programs
    leaf = obs.counter("compile.count", ("entry",)).labels(
        entry="test.resync")
    assert leaf.value == 2.0
    obs.default_registry().reset()
    assert leaf.value == 0.0
    watchdog.resync_counter()
    assert leaf.value == watchdog.compile_counts()["test.resync"] == 2
    # idempotent: a second resync adds nothing
    watchdog.resync_counter()
    assert leaf.value == 2.0


def test_profiler_without_exporter_strands_no_marks():
    """Marks exist solely for the trace-export stream: a Profiler with no
    on_trace_ready must not grow the module-global mark buffer (it would
    leak for the life of the process with nothing draining it)."""
    from paddle_tpu import profiler as prof

    obs.counter("serving.generated_tokens").inc()
    before = len(prof._metric_marks)
    p = prof.Profiler()          # no on_trace_ready
    p.start()
    p.stop()
    assert len(prof._metric_marks) == before


def test_watchdog_entries_are_weakly_held():
    import gc
    f = watchdog.watch("test.weak", jax.jit(lambda x: x + 1))
    f(jnp.ones((2,)))
    assert watchdog.compile_counts().get("test.weak") == 1
    del f
    gc.collect()
    assert "test.weak" not in watchdog.compile_counts()


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _sample_registry():
    reg = Registry(catalog=None)
    reg.counter("requests.total", ("kind",)).labels(kind="ok").inc(3)
    reg.gauge("depth").set(2)
    h = reg.histogram("lat.seconds")
    for v in (0.01, 0.02, 0.04):
        h.observe(v)
    return reg


def test_prometheus_text_format():
    text = exporters.to_prometheus(_sample_registry())
    assert '# TYPE requests_total counter' in text
    assert 'requests_total{kind="ok"} 3.0' in text
    assert '# TYPE lat_seconds summary' in text
    assert 'lat_seconds{quantile="0.50"}' in text
    assert 'lat_seconds_count 3' in text
    assert '# TYPE depth gauge' in text


def test_jsonl_snapshot_roundtrip(tmp_path):
    p = tmp_path / "metrics.jsonl"
    exp = exporters.JsonlExporter(str(p))
    exp.write(_sample_registry())
    exp.write(_sample_registry())
    lines = [json.loads(l) for l in p.read_text().splitlines()]
    assert len(lines) == 2
    assert lines[0]["ts"] > 0
    m = lines[0]["metrics"]
    assert m["requests.total"]["series"][0]["value"] == 3.0
    assert m["lat.seconds"]["series"][0]["count"] == 3
    assert {"p50", "p95", "p99"} <= set(m["lat.seconds"]["series"][0])


def test_chrome_trace_export_carries_metric_marks(tmp_path):
    from paddle_tpu import profiler as prof

    obs.counter("serving.generated_tokens").inc(7)
    p = prof.Profiler(
        on_trace_ready=prof.export_chrome_tracing(str(tmp_path)))
    p.start()
    with prof.RecordEvent("span_under_metrics"):
        pass
    p.stop()
    doc = json.load(open(p._last_export))
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert counters, "no metric marks in the chrome trace"
    names = {e["name"] for e in counters}
    assert any(n.startswith("serving.generated_tokens") for n in names)
    assert all("value" in e["args"] for e in counters)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _write_snapshots(path):
    exp = exporters.JsonlExporter(str(path))
    exp.write(_sample_registry())
    exp.write(_sample_registry())


def test_cli_dump_prom_and_json(tmp_path, capsys):
    from paddle_tpu.observability.__main__ import main

    p = tmp_path / "m.jsonl"
    _write_snapshots(p)
    assert main(["dump", "--file", str(p)]) == 0
    out = capsys.readouterr().out
    assert 'requests_total{kind="ok"} 3.0' in out
    assert main(["dump", "--file", str(p), "--format", "json"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out)["requests.total"]["type"] == "counter"


def test_cli_dump_missing_file_exits_cleanly(tmp_path, capsys):
    from paddle_tpu.observability.__main__ import main

    rc = main(["dump", "--file", str(tmp_path / "never_written.jsonl")])
    assert rc == 1
    err = capsys.readouterr().err
    assert "no snapshots" in err


def test_cli_tail_summarizes_lines(tmp_path, capsys):
    from paddle_tpu.observability.__main__ import main

    p = tmp_path / "m.jsonl"
    _write_snapshots(p)
    assert main(["tail", "--file", str(p)]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 2
    assert "requests.total{kind=ok}=3" in out[0]
    assert "lat.seconds: n=3" in out[0]


def test_cli_serve_exposes_prometheus(tmp_path):
    from paddle_tpu.observability.__main__ import make_server

    p = tmp_path / "m.jsonl"
    _write_snapshots(p)
    srv = make_server(str(p), port=0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        url = "http://127.0.0.1:%d/metrics" % srv.server_address[1]
        body = urllib.request.urlopen(url, timeout=5).read().decode()
        assert 'requests_total{kind="ok"} 3.0' in body
        assert urllib.request.urlopen(
            "http://127.0.0.1:%d/" % srv.server_address[1],
            timeout=5).status == 200
    finally:
        srv.shutdown()
        srv.server_close()


def _serve_get(srv, path="/metrics"):
    url = "http://127.0.0.1:%d%s" % (srv.server_address[1], path)
    resp = urllib.request.urlopen(url, timeout=5)
    return resp, resp.read().decode()


def test_serve_in_process_registry_real_get():
    """ISSUE-11 satellite: the in_process=True server (the test-drivable
    mode make_server was built with but nothing exercised) must serve
    the LIVE default registry over a real HTTP GET, with the Prometheus
    content-type and a 404 off the known paths."""
    from paddle_tpu.observability.__main__ import make_server

    obs.counter("serving.generated_tokens").inc(5)
    srv = make_server(None, port=0, in_process=True)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        resp, body = _serve_get(srv)
        assert resp.status == 200
        ctype = resp.headers["Content-Type"]
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype and "charset=utf-8" in ctype
        assert int(resp.headers["Content-Length"]) == len(body.encode())
        assert "serving_generated_tokens" in body
        # the live registry is served: a new recording shows on re-GET
        obs.counter("serving.generated_tokens").inc(2)
        _resp, body2 = _serve_get(srv)
        assert body2 != body
        with pytest.raises(urllib.error.HTTPError) as e:
            _serve_get(srv, "/nope")
        assert e.value.code == 404
    finally:
        srv.shutdown()
        srv.server_close()


def test_serve_file_mode_serves_newest_snapshot(tmp_path):
    """File-backed serve must render the NEWEST snapshot line (the
    tail), not the first, and tolerate a missing file with an empty
    body."""
    from paddle_tpu.observability.__main__ import make_server

    p = tmp_path / "m.jsonl"
    exp = exporters.JsonlExporter(str(p))
    reg1 = Registry(catalog=None)
    reg1.gauge("depth").set(1)
    exp.write(reg1)
    reg1.gauge("depth").set(42)      # newest line carries 42
    exp.write(reg1)
    srv = make_server(str(p), port=0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        _resp, body = _serve_get(srv)
        assert "depth 42.0" in body and "depth 1.0" not in body
    finally:
        srv.shutdown()
        srv.server_close()
    missing = make_server(str(tmp_path / "never.jsonl"), port=0)
    t = threading.Thread(target=missing.serve_forever, daemon=True)
    t.start()
    try:
        resp, body = _serve_get(missing)
        assert resp.status == 200 and body == ""
    finally:
        missing.shutdown()
        missing.server_close()


# ---------------------------------------------------------------------------
# queue_wait satellite
# ---------------------------------------------------------------------------

def test_scheduler_splits_queue_wait_out_of_ttft():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.engine import DecodeEngine
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)

    cfg = GPTConfig.tiny()
    cfg.hidden_dropout_prob = cfg.attention_dropout_prob = 0.0
    engine = DecodeEngine(GPTForCausalLM(cfg), num_slots=1, max_len=64,
                          seed=0)
    sched = ContinuousBatchingScheduler(engine)
    rng = np.random.default_rng(2)
    # 3 requests into ONE slot: the 2nd/3rd must QUEUE while the earlier
    # ones decode, so their queue_wait is necessarily positive
    for _ in range(3):
        sched.submit(Request(prompt=rng.integers(0, cfg.vocab_size, (6,)),
                             max_new_tokens=4, temperature=0.0))
    results = sched.run()
    assert len(results) == 3
    by_rid = [results[r] for r in sorted(results)]
    for r in by_rid:
        assert r.queue_wait >= 0.0
        # TTFT still includes the queue component (documented contract),
        # so the split piece can never exceed it
        assert r.ttft >= r.queue_wait
    assert by_rid[1].queue_wait > 0.0
    assert by_rid[2].queue_wait > by_rid[1].queue_wait


# ---------------------------------------------------------------------------
# bench schema validator (tools/bench_schema.py)
# ---------------------------------------------------------------------------

def _bench_schema():
    import importlib.util
    import pathlib
    p = pathlib.Path(__file__).resolve().parent.parent / "tools" \
        / "bench_schema.py"
    spec = importlib.util.spec_from_file_location("bench_schema", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_schema_accepts_wrapper_file_and_new_block(tmp_path):
    bs = _bench_schema()
    # a wrapper file of the shape the round 1-5 driver wrote (no BENCH_*
    # file is committed any more — the records were removed in PR 21)
    wrapper = tmp_path / "BENCH_r01.json"
    wrapper.write_text(json.dumps({
        "n": 1, "cmd": "python bench.py", "rc": 0, "tail": "",
        "parsed": {"metric": "tokens/sec/chip (GPT-2 345M bf16 train)",
                   "value": 1.0, "unit": "tokens/s", "vs_baseline": 0.5}}))
    bs.validate_path(str(wrapper))        # raises on schema violation
    line = {
        "metric": "decode_tokens_per_sec", "value": 10.0, "unit": "tok/s",
        "compile_counts": {"decode": 1, "prefill": 2},
        "metrics": {
            "histograms": {"serving.ttft_seconds": {
                "p50_ms": 1.0, "p95_ms": 2.0, "p99_ms": 3.0, "count": 5}},
            "compile_counts": {"serving.decode": 1},
        },
    }
    bs.validate_line(line, "<t>", ["serving.decode"])


def test_bench_schema_rejects_malformed_lines():
    bs = _bench_schema()
    ok_metrics = {"histograms": {}, "compile_counts": {}}
    for bad in (
        {"value": 1.0, "unit": "x"},                      # no metric
        {"metric": "m", "value": "fast", "unit": "x"},    # value not num
        {"metric": "m", "value": 1.0, "unit": "x",
         "compile_counts": {"decode": 0}},                # zero compiles
        {"metric": "m", "value": 1.0, "unit": "x",
         "metrics": {"histograms": {"h": {"p50_ms": 3.0, "p95_ms": 2.0,
                                          "p99_ms": 4.0, "count": 1}},
                     "compile_counts": {}}},              # unordered pcts
        {"metric": "m", "value": 1.0, "unit": "x",
         "metrics": {"histograms": {}}},                  # no compile_counts
    ):
        import pytest as _pt
        with _pt.raises(bs.SchemaError):
            bs.validate_line(bad, "<t>")
    # --expect-compile-once fails on a 2-program entry
    import pytest as _pt
    with _pt.raises(bs.SchemaError, match="expected exactly 1"):
        bs.validate_line(
            {"metric": "m", "value": 1.0, "unit": "x",
             "metrics": {"histograms": {},
                         "compile_counts": {"serving.decode": 2}}},
            "<t>", ["serving.decode"])
    with _pt.raises(bs.SchemaError, match="rc"):
        bs.validate_wrapper({"rc": 1, "parsed": ok_metrics}, "<t>")


def _traj_entry(tmp_path, name, value, backend, decode_compiles=1,
                metric="decode_tokens_per_sec", layout="paged",
                kv_dtype=None, spec=None, kv_host=None, repeat_ttft=None,
                host_hit_pages=None, replicas=None, overlap_comm=None):
    line = {"metric": metric, "value": value, "unit": "tok/s",
            "cache_layout": layout,
            "compile_counts": {"decode": decode_compiles, "prefill": 1},
            "metrics": {"histograms": {},
                        "compile_counts":
                            {"serving.decode": decode_compiles}},
            "config": {"backend": backend, "model": "tiny"}}
    if kv_dtype is not None:
        line["kv_dtype"] = kv_dtype
    if spec is not None:
        line["spec"] = spec
    if kv_host is not None:
        line["kv_host"] = kv_host
        if kv_host == "on" and host_hit_pages is None:
            host_hit_pages = 2      # schema: an on line must have hits
    if repeat_ttft is not None:
        line["repeat_ttft_ms"] = repeat_ttft
    if host_hit_pages is not None:
        line["host_hit_pages"] = host_hit_pages
    if replicas is not None:
        line["replicas"] = replicas
    if overlap_comm is not None:
        line["overlap_comm"] = overlap_comm
    p = tmp_path / name
    p.write_text(json.dumps({"n": 1, "cmd": "bench", "rc": 0,
                             "parsed": line}))
    return str(p)


def test_trajectory_mode_gates_compile_counts_and_regression(tmp_path):
    bs = _bench_schema()
    # healthy series: CPU smoke + two chip rounds within 3%
    paths = [
        _traj_entry(tmp_path, "BENCH_decode_r01.json", 50.0, "cpu"),
        _traj_entry(tmp_path, "BENCH_decode_r02.json", 1000.0, "tpu"),
        _traj_entry(tmp_path, "BENCH_decode_r03.json", 985.0, "tpu"),
    ]
    assert bs.check_trajectory(paths) == []
    # >3% on-chip drop fails, and names both files
    paths.append(_traj_entry(tmp_path, "BENCH_decode_r04.json", 900.0,
                             "tpu"))
    fails = bs.check_trajectory(paths)
    assert len(fails) == 1 and "regression" in fails[0]
    assert "BENCH_decode_r04" in fails[0] and "BENCH_decode_r03" in fails[0]
    # a CPU entry never perf-gates...
    cpu_drop = [paths[0],
                _traj_entry(tmp_path, "BENCH_decode_r09.json", 1.0, "cpu")]
    assert bs.check_trajectory(cpu_drop) == []
    # ...but its compile counts DO gate (retrace detection is
    # backend-independent)
    bad = [_traj_entry(tmp_path, "BENCH_decode_r10.json", 50.0, "cpu",
                       decode_compiles=2)]
    fails = bs.check_trajectory(bad)
    assert fails and "compile-once" in fails[0]


def test_trajectory_mode_separates_layouts_and_writes(tmp_path):
    bs = _bench_schema()
    # slotted->paged A/B entries are DIFFERENT series legs: a paged
    # round slower than the previous slotted round must not trip the
    # regression gate (only like-for-like consecutive entries compare)
    paths = [
        _traj_entry(tmp_path, "BENCH_decode_r01.json", 1000.0, "tpu",
                    layout="slotted"),
        _traj_entry(tmp_path, "BENCH_decode_r02.json", 700.0, "tpu",
                    layout="paged"),
        _traj_entry(tmp_path, "BENCH_decode_r03.json", 690.0, "tpu",
                    layout="paged"),
    ]
    assert bs.check_trajectory(paths) == []
    out = tmp_path / "traj.json"
    assert bs.check_trajectory(paths, write=str(out)) == []
    doc = json.loads(out.read_text())
    assert doc["schema"] == 1
    assert len(doc["series"]["decode_tokens_per_sec"]) == 3
    # an INTERLEAVED series still gates like-for-like: each layout keeps
    # its own cursor, so a paged round regressing vs the LAST PAGED
    # round fails even with slotted rounds in between (a single cursor
    # would skip every mismatched pair and lose its anchor — gate inert)
    interleaved = [
        _traj_entry(tmp_path, "BENCH_decode_r11.json", 1000.0, "tpu",
                    layout="slotted"),
        _traj_entry(tmp_path, "BENCH_decode_r12.json", 700.0, "tpu",
                    layout="paged"),
        _traj_entry(tmp_path, "BENCH_decode_r13.json", 985.0, "tpu",
                    layout="slotted"),
        _traj_entry(tmp_path, "BENCH_decode_r14.json", 500.0, "tpu",
                    layout="paged"),
    ]
    fails = bs.check_trajectory(interleaved)
    assert len(fails) == 1 and "regression" in fails[0]
    assert "BENCH_decode_r14" in fails[0] and "BENCH_decode_r12" in fails[0]


def test_trajectory_cursor_keys_on_kv_dtype_and_spec(tmp_path):
    """ISSUE-8 cursor key: the A/B matrix interleaves (kv_dtype, spec)
    lines in one trajectory — int8 is legitimately differently-paced
    than bf16 and a spec line than a non-spec one, so each combination
    keeps its OWN regression cursor; and a real like-for-like drop
    still fails with matrix lines in between."""
    bs = _bench_schema()
    # int8 slower than the preceding bf16 line: different legs, no fail
    mixed = [
        _traj_entry(tmp_path, "BENCH_decode_r21.json", 1000.0, "tpu",
                    kv_dtype="bf16", spec=0),
        _traj_entry(tmp_path, "BENCH_decode_r22.json", 600.0, "tpu",
                    kv_dtype="int8", spec=0),
        _traj_entry(tmp_path, "BENCH_decode_r23.json", 400.0, "tpu",
                    kv_dtype="int8", spec=4),
    ]
    assert bs.check_trajectory(mixed) == []
    # a second round regressing ONLY on the (int8, spec=4) leg fails,
    # anchored to the last entry of THAT leg — not to the bf16 line
    # that sits between them
    mixed += [
        _traj_entry(tmp_path, "BENCH_decode_r24.json", 1010.0, "tpu",
                    kv_dtype="bf16", spec=0),
        _traj_entry(tmp_path, "BENCH_decode_r25.json", 300.0, "tpu",
                    kv_dtype="int8", spec=4),
    ]
    fails = bs.check_trajectory(mixed)
    assert len(fails) == 1 and "regression" in fails[0]
    assert "BENCH_decode_r25" in fails[0] and "BENCH_decode_r23" in fails[0]
    # legacy lines (no kv_dtype/spec fields) key their own cursor and
    # never compare against the new matrix legs
    legacy = [
        _traj_entry(tmp_path, "BENCH_decode_r31.json", 900.0, "tpu"),
        _traj_entry(tmp_path, "BENCH_decode_r32.json", 500.0, "tpu",
                    kv_dtype="int8", spec=0),
        _traj_entry(tmp_path, "BENCH_decode_r33.json", 895.0, "tpu"),
    ]
    assert bs.check_trajectory(legacy) == []


def test_trajectory_kv_host_cursor_and_repeat_ttft_gate(tmp_path):
    """ISSUE-17 cursor + gate: the --kv-host arms key their own cursors
    (the on arm pacing differently than off is the point of the A/B,
    not a regression), legacy lines without the field keep theirs, and
    the repeat-prompt TTFT gate fails a like-for-like on-chip pair that
    slid >3% — while staying disarmed on CPU smoke lines."""
    bs = _bench_schema()
    # on arm slower than the off arm it follows: different legs, no
    # fail; a legacy (pre-tier) line in between keys its own cursor too
    mixed = [
        _traj_entry(tmp_path, "BENCH_decode_r41.json", 1000.0, "tpu",
                    kv_host="off", repeat_ttft=40.0),
        _traj_entry(tmp_path, "BENCH_decode_r42.json", 700.0, "tpu",
                    kv_host="on", repeat_ttft=12.0),
        _traj_entry(tmp_path, "BENCH_decode_r43.json", 950.0, "tpu"),
    ]
    assert bs.check_trajectory(mixed) == []
    # a second on-arm round whose repeat TTFT slid >3% fails against
    # the LAST on-arm entry, with the off arm and legacy lines between
    mixed += [
        _traj_entry(tmp_path, "BENCH_decode_r44.json", 1005.0, "tpu",
                    kv_host="off", repeat_ttft=40.5),
        _traj_entry(tmp_path, "BENCH_decode_r45.json", 702.0, "tpu",
                    kv_host="on", repeat_ttft=14.0),
    ]
    fails = bs.check_trajectory(mixed)
    assert len(fails) == 1 and "repeat-prompt TTFT" in fails[0]
    assert "BENCH_decode_r45" in fails[0] and "BENCH_decode_r42" in fails[0]
    # CPU smoke never arms the repeat gate (compile-dominated window)
    cpu = [
        _traj_entry(tmp_path, "BENCH_decode_r51.json", 50.0, "cpu",
                    kv_host="on", repeat_ttft=10.0),
        _traj_entry(tmp_path, "BENCH_decode_r52.json", 50.0, "cpu",
                    kv_host="on", repeat_ttft=300.0),
    ]
    assert bs.check_trajectory(cpu) == []
    # line shape: an on line claiming zero host hits is rejected — the
    # bench would be gating a tier that served nothing
    with pytest.raises(bs.SchemaError, match="host_hit_pages"):
        bs.validate_line({"metric": "decode_tokens_per_sec",
                          "value": 1.0, "unit": "tok/s",
                          "kv_host": "on", "host_hit_pages": 0},
                         "<line>")
    with pytest.raises(bs.SchemaError, match="kv_host"):
        bs.validate_line({"metric": "decode_tokens_per_sec",
                          "value": 1.0, "unit": "tok/s",
                          "kv_host": True}, "<line>")


def test_trajectory_overlap_comm_cursor_isolation(tmp_path):
    """ISSUE-20 cursor: the --overlap-comm arms key their own regression
    cursors (the ring trading launches for hidden transfer paces
    differently than the monolithic collective — that is the A/B), a
    real like-for-like drop inside ONE arm still fails, and legacy
    lines without the field never gate against either arm."""
    bs = _bench_schema()
    mixed = [
        _traj_entry(tmp_path, "BENCH_decode_r71.json", 900.0, "tpu"),
        _traj_entry(tmp_path, "BENCH_decode_r72.json", 1000.0, "tpu",
                    overlap_comm="off"),
        _traj_entry(tmp_path, "BENCH_decode_r73.json", 700.0, "tpu",
                    overlap_comm="on"),
        _traj_entry(tmp_path, "BENCH_decode_r74.json", 890.0, "tpu"),
    ]
    assert bs.check_trajectory(mixed) == []
    # the on arm regressing vs ITS last entry fails, anchored past the
    # off-arm and legacy lines in between
    mixed.append(_traj_entry(tmp_path, "BENCH_decode_r75.json", 600.0,
                             "tpu", overlap_comm="on"))
    fails = bs.check_trajectory(mixed)
    assert len(fails) == 1 and "regression" in fails[0]
    assert "BENCH_decode_r75" in fails[0] and "BENCH_decode_r73" in fails[0]
    # line shape: only the on/off spellings are archivable
    with pytest.raises(bs.SchemaError, match="overlap_comm"):
        bs.validate_line({"metric": "decode_tokens_per_sec",
                          "value": 1.0, "unit": "tok/s",
                          "overlap_comm": True}, "<line>")


def test_trajectory_replicas_cursor_and_fleet_compile_budget(tmp_path):
    """ISSUE-19 fleet axis: --replicas N lines key their OWN regression
    cursor (a per-replica goodput number paces differently than the
    single-engine line — that is the A/B, not a regression) while
    legacy lines without the field keep theirs; and the compile-once
    gate scales to once PER REPLICA on fleet lines only — a summed
    count of N over N replicas is the contract, the same count on a
    single-engine line is a retrace."""
    bs = _bench_schema()
    # a 2-replica line slower than the legacy single-engine anchor it
    # follows: different legs, no fail — and the next legacy line still
    # gates against ITS cursor, not the fleet line in between
    mixed = [
        _traj_entry(tmp_path, "BENCH_decode_r61.json", 1000.0, "tpu"),
        _traj_entry(tmp_path, "BENCH_decode_r62.json", 600.0, "tpu",
                    replicas=2, decode_compiles=2),
        _traj_entry(tmp_path, "BENCH_decode_r63.json", 995.0, "tpu"),
    ]
    assert bs.check_trajectory(mixed) == []
    # a second fleet round regressing on the replicas=2 leg fails,
    # anchored to the last FLEET entry — not the legacy line between
    mixed.append(_traj_entry(tmp_path, "BENCH_decode_r64.json", 400.0,
                             "tpu", replicas=2, decode_compiles=2))
    fails = bs.check_trajectory(mixed)
    assert len(fails) == 1 and "regression" in fails[0]
    assert "BENCH_decode_r64" in fails[0] and "BENCH_decode_r62" in fails[0]
    # compile-once scales with the fleet: 2 compiles over 2 replicas
    # passes (asserted by the healthy series above), the SAME count on
    # a line without the field is a retrace and fails
    bad = [_traj_entry(tmp_path, "BENCH_decode_r71.json", 50.0, "cpu",
                       decode_compiles=2)]
    fails = bs.check_trajectory(bad)
    assert fails and all("compile-once" in f for f in fails)
    # and a fleet line under-compiling (one cold replica never drove its
    # decode program) fails too — once per replica, no more, no less
    cold = [_traj_entry(tmp_path, "BENCH_decode_r72.json", 50.0, "cpu",
                        replicas=2, decode_compiles=1)]
    fails = bs.check_trajectory(cold)
    assert fails and all("compile-once" in f for f in fails)
    assert "2 replica" in fails[0]


# -- BENCH_serve schema + trajectory gates (ISSUE 13) -----------------------

def _serve_line(value, backend, qps=8.0, mix="short", ttft_p99=50.0,
                overlap=True, **over):
    line = {"metric": "serve_goodput_tokens_per_sec", "value": value,
            "unit": "tok/s", "qps": qps, "mix": mix,
            "cache_layout": "paged", "kv_dtype": "bf16", "spec": 0,
            "tp": 1, "overlap": overlap,
            "ttft_p50_ms": 10.0, "ttft_p99_ms": ttft_p99,
            "tpot_p50_ms": 2.0, "tpot_p99_ms": 4.0, "shed_rate": 0.0,
            "metrics": {"histograms": {},
                        "compile_counts": {"serving.decode": 1}},
            "config": {"backend": backend, "model": "tiny_d64"}}
    line.update(over)
    return line


def _serve_entry(tmp_path, name, *a, **kw):
    p = tmp_path / name
    p.write_text(json.dumps({"n": 1, "cmd": "bench_serve", "rc": 0,
                             "parsed": _serve_line(*a, **kw)}))
    return str(p)


def test_serve_line_schema():
    bs = _bench_schema()
    bs.validate_line(_serve_line(100.0, "cpu"), "<t>",
                     ["serving.decode"])
    import pytest as _pt
    for mutate in (
        lambda l: l.pop("ttft_p99_ms"),            # missing p99
        lambda l: l.pop("mix"),                    # missing mix
        lambda l: l.pop("qps"),                    # missing qps
        lambda l: l.update(shed_rate=1.5),         # impossible rate
        lambda l: l.update(qps=0),                 # zero offered rate
        lambda l: l.update(ttft_p50_ms=99.0),      # p50 > p99
    ):
        bad = _serve_line(100.0, "cpu")
        mutate(bad)
        with _pt.raises(bs.SchemaError):
            bs.validate_line(bad, "<t>")
    # decode lines are untouched by the serve field requirements
    bs.validate_line({"metric": "decode_tokens_per_sec", "value": 1.0,
                      "unit": "tok/s"}, "<t>")


def test_serve_trajectory_gates_goodput_and_p99_like_for_like(tmp_path):
    """Serve cursors key on (qps, mix) on top of the decode axes: a
    qps=16 line never gates against qps=4; a like-for-like goodput drop
    OR p99-TTFT growth fails; CPU lines never gate."""
    bs = _bench_schema()
    ok = [
        _serve_entry(tmp_path, "BENCH_serve_r01.json", 100.0, "tpu",
                     qps=4.0),
        _serve_entry(tmp_path, "BENCH_serve_r02.json", 60.0, "tpu",
                     qps=16.0, ttft_p99=200.0),   # saturated point: its
        _serve_entry(tmp_path, "BENCH_serve_r03.json", 99.0, "tpu",
                     qps=4.0),                    # own cursor, no fail
    ]
    assert bs.check_trajectory(ok) == []
    # like-for-like goodput drop fails, anchored to the SAME (qps, mix)
    drop = ok + [_serve_entry(tmp_path, "BENCH_serve_r04.json", 80.0,
                              "tpu", qps=4.0)]
    fails = bs.check_trajectory(drop)
    assert len(fails) == 1 and "BENCH_serve_r03" in fails[0]
    # p99-TTFT growth fails even with goodput held
    tail = ok + [_serve_entry(tmp_path, "BENCH_serve_r05.json", 99.5,
                              "tpu", qps=4.0, ttft_p99=60.0)]
    fails = bs.check_trajectory(tail)
    assert len(fails) == 1 and "p99 TTFT" in fails[0]
    # CPU smoke points never perf-gate
    cpu = [_serve_entry(tmp_path, "BENCH_serve_s1.json", 100.0, "cpu"),
           _serve_entry(tmp_path, "BENCH_serve_s2.json", 10.0, "cpu")]
    assert bs.check_trajectory(cpu) == []
    # a different mix is a different cursor
    mixes = [_serve_entry(tmp_path, "BENCH_serve_m1.json", 100.0, "tpu",
                          mix="short"),
             _serve_entry(tmp_path, "BENCH_serve_m2.json", 40.0, "tpu",
                          mix="long")]
    assert bs.check_trajectory(mixes) == []


def test_serve_line_schema_disagg_and_wave_blocks():
    """ISSUE-15 optional serve-line fields: a disagg line must carry its
    handoff bytes, the wave block must be well-formed, and legacy lines
    without either validate clean (regression)."""
    bs = _bench_schema()
    import pytest as _pt
    # legacy line (no disagg/wave fields) stays valid
    bs.validate_line(_serve_line(100.0, "cpu"), "<t>")
    # disagg line with handoff accounting + compile-once handoff entries
    good = _serve_line(
        100.0, "cpu", disagg=True, handoff_bytes=4096, handoffs=3,
        wave={"mix": "prefill_heavy", "requests": 4, "completed": 4,
              "quiet_gaps": 30, "wave_gaps": 20,
              "quiet_tpot_p50_ms": 2.0, "quiet_tpot_p99_ms": 4.0,
              "wave_tpot_p50_ms": 2.1, "wave_tpot_p99_ms": 4.2})
    good["metrics"]["compile_counts"].update(
        {"serving.kv_export": 1, "serving.kv_import": 1})
    bs.validate_line(good, "<t>", ["serving.kv_export",
                                   "serving.kv_import"])
    for mutate in (
        lambda l: l.pop("handoff_bytes"),          # disagg needs bytes
        lambda l: l.update(handoff_bytes=-1),
        lambda l: l.update(disagg="yes"),          # not a bool
        lambda l: l["wave"].pop("wave_tpot_p99_ms"),
        lambda l: l["wave"].update(quiet_tpot_p50_ms=9.0),  # p50 > p99
    ):
        bad = _serve_line(
            100.0, "cpu", disagg=True, handoff_bytes=4096,
            wave={"quiet_tpot_p50_ms": 2.0, "quiet_tpot_p99_ms": 4.0,
                  "wave_tpot_p50_ms": 2.1, "wave_tpot_p99_ms": 4.2})
        mutate(bad)
        with _pt.raises(bs.SchemaError):
            bs.validate_line(bad, "<t>")


def test_serve_trajectory_cursor_keys_on_disagg(tmp_path):
    """ISSUE-15 serve axis: colocated and disagg lines keep separate
    cursors (a role-split arm is a different operating point), and
    legacy lines without the field keep their own."""
    bs = _bench_schema()
    mixed = [
        _serve_entry(tmp_path, "BENCH_serve_d1.json", 100.0, "tpu",
                     disagg=False),
        _serve_entry(tmp_path, "BENCH_serve_d2.json", 70.0, "tpu",
                     disagg=True, handoff_bytes=1024),
        _serve_entry(tmp_path, "BENCH_serve_d3.json", 99.5, "tpu",
                     disagg=False),
        # legacy (pre-disagg) line: its own cursor, not the False one
        _serve_entry(tmp_path, "BENCH_serve_d4.json", 50.0, "tpu"),
    ]
    assert bs.check_trajectory(mixed) == []
    # a like-for-like drop on the disagg leg still fails
    mixed.append(_serve_entry(tmp_path, "BENCH_serve_d5.json", 60.0,
                              "tpu", disagg=True, handoff_bytes=1024))
    fails = bs.check_trajectory(mixed)
    assert len(fails) == 1 and "BENCH_serve_d2" in fails[0]


def test_trajectory_cursor_keys_on_overlap(tmp_path):
    """ISSUE-13 decode axis: a sync-loop (--overlap off) A/B line is
    legitimately slower than the overlapped default — each keeps its
    own cursor; legacy lines (no overlap field) keep theirs."""
    bs = _bench_schema()
    def entry(name, value, overlap):
        p = tmp_path / name
        line = {"metric": "decode_tokens_per_sec", "value": value,
                "unit": "tok/s", "cache_layout": "paged",
                "overlap": overlap,
                "config": {"backend": "tpu", "model": "tiny"}}
        p.write_text(json.dumps({"n": 1, "cmd": "b", "rc": 0,
                                 "parsed": line}))
        return str(p)
    mixed = [entry("BENCH_decode_o1.json", 1000.0, True),
             entry("BENCH_decode_o2.json", 800.0, False),
             entry("BENCH_decode_o3.json", 1005.0, True)]
    assert bs.check_trajectory(mixed) == []
    # a like-for-like drop on the overlapped leg still fails
    mixed.append(entry("BENCH_decode_o4.json", 900.0, True))
    fails = bs.check_trajectory(mixed)
    assert len(fails) == 1 and "BENCH_decode_o3" in fails[0]


def test_flush_writes_default_registry(tmp_path):
    obs.counter("serving.generated_tokens").inc()
    out = obs.flush(str(tmp_path / "snap.jsonl"))
    doc = json.loads(open(out).read().splitlines()[-1])
    assert "serving.generated_tokens" in doc["metrics"]
