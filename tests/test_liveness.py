"""The liveness-watchdog + cross-host-telemetry suite (ISSUE 14).

Covers: beacon semantics and the disabled-path no-op identity on the
scheduler hot loop (acceptance), stall detection with all-thread-stack
flight dumps, the injected ``Hang`` chaos scenarios (checkpoint write +
scheduler step, post-hang serviceability), deadline resolution, the
hard-exit rc path (subprocess), the SIGQUIT manual postmortem
(subprocess), uncaught-worker-thread flight routing, and the
aggregation half: per-host snapshot publish through the distributed
store, the host-0 merge with straggler detection, the ``cluster`` CLI
exit-code discipline, and the 2-process store-backed smoke CI runs.
"""
import json
import os
import signal
import subprocess
import sys
import tempfile
import textwrap
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import aggregate, flight, liveness
from paddle_tpu.observability import registry as reg_mod
from paddle_tpu.robustness.faultpoints import FaultPlan, Hang, chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_liveness_and_flight():
    """Every test starts and ends with liveness + flight disarmed (the
    process default) so suites can run in any order."""
    liveness.disable()
    flight.disable()
    yield
    liveness.disable()
    flight.disable()


@pytest.fixture()
def armed(tmp_path):
    """Flight recorder + a monitor the test drives via check_now()."""
    rec = flight.enable(dir=str(tmp_path))
    mon = liveness.enable(start=False)
    return rec, mon


@pytest.fixture(scope="module")
def gpt_engine():
    """ONE engine for the whole module (tier-1 wall budget): the engine
    holds no liveness state — schedulers fetch the beacon — so every
    test builds its own scheduler around the shared compiled programs."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.engine import DecodeEngine
    cfg = GPTConfig.tiny()
    cfg.hidden_dropout_prob = cfg.attention_dropout_prob = 0.0
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    engine = DecodeEngine(model, num_slots=2, max_len=64, seed=0,
                          page_size=8)
    return model, engine


def _sched(engine):
    from paddle_tpu.serving.scheduler import ContinuousBatchingScheduler
    engine.reset()
    return ContinuousBatchingScheduler(engine)


# ---------------------------------------------------------------------------
# beacon semantics
# ---------------------------------------------------------------------------

def test_beacon_units_guard_pulse_and_declared_registry():
    mon = liveness.enable(start=False)
    liveness.declare_beacon("test.unit", "suite probe")
    b = liveness.beacon("test.unit")
    assert liveness.beacon("test.unit") is b          # one object per name
    assert b.count == 0 and b.inflight == 0
    with b:
        assert b.inflight == 1
    assert b.inflight == 0 and b.count == 1           # exit counts
    b.pulse()
    assert b.count == 2
    before = b.last_ns
    b.pulse()
    assert b.last_ns >= before                        # pulse re-stamps
    # an op that RAISES still completes (only a hang is a stall)
    with pytest.raises(RuntimeError):
        with b:
            raise RuntimeError("x")
    assert b.inflight == 0 and b.count == 4
    # undeclared names fail at fetch (bounded liveness.stalls labels)
    with pytest.raises(ValueError, match="unknown liveness beacon"):
        mon.beacon("test.never_declared")


def test_production_beacons_are_declared():
    """The instrumented modules declare their beacons at import time —
    the registry mirrors the instrumentation (OBSERVABILITY.md's
    table is generated from the same names)."""
    import paddle_tpu.distributed.store      # noqa: F401
    import paddle_tpu.hapi                   # noqa: F401
    import paddle_tpu.incubate.checkpoint    # noqa: F401
    import paddle_tpu.jit                    # noqa: F401
    import paddle_tpu.kernels.autotune       # noqa: F401
    import paddle_tpu.serving.frontend       # noqa: F401
    import paddle_tpu.serving.scheduler      # noqa: F401
    expected = {"train.step", "train.fit_batch", "serve.scheduler_step",
                "serve.frontend_sched", "serve.frontend_loop",
                "checkpoint.writer", "store.op", "autotune.tune"}
    assert expected <= set(liveness.BEACONS), (
        expected - set(liveness.BEACONS))
    for name in expected:
        assert liveness.BEACONS[name]["doc"], name


def test_disabled_is_noop_identity_on_scheduler_hot_loop(monkeypatch,
                                                         gpt_engine):
    """ACCEPTANCE: with liveness off (the default) every beacon call
    site is the shared no-op singleton by IDENTITY, and the decode/
    prefill compile counts are unchanged under the strict watchdog."""
    from paddle_tpu.serving.scheduler import Request
    assert liveness.active() is None
    assert liveness.beacon("serve.scheduler_step") is liveness.NOOP_BEACON
    monkeypatch.setenv("PADDLE_TPU_STRICT_COMPILE", "1")
    _model, engine = gpt_engine
    sched = _sched(engine)
    assert sched._beacon is liveness.NOOP_BEACON
    rng = np.random.default_rng(0)
    for _ in range(3):
        sched.submit(Request(prompt=rng.integers(0, 100, (8,)),
                             max_new_tokens=4, temperature=0.0))
    out = sched.run()
    assert len(out) == 3
    assert engine.decode_compile_count == 1
    assert engine.prefill_compile_count == 1


def test_enabled_compile_counts_unchanged_under_strict(monkeypatch,
                                                       gpt_engine):
    """Arming liveness is host-side only: same programs, same compile
    counts, strict watchdog quiet."""
    from paddle_tpu.serving.scheduler import Request
    monkeypatch.setenv("PADDLE_TPU_STRICT_COMPILE", "1")
    liveness.enable(start=False)
    _model, engine = gpt_engine
    sched = _sched(engine)
    assert sched._beacon is not liveness.NOOP_BEACON
    rng = np.random.default_rng(0)
    for _ in range(3):
        sched.submit(Request(prompt=rng.integers(0, 100, (8,)),
                             max_new_tokens=4, temperature=0.0))
    sched.run()
    assert engine.decode_compile_count == 1
    assert engine.prefill_compile_count == 1
    st = liveness.state()
    assert st["serve.scheduler_step"]["count"] >= 3    # guarded per step
    assert st["serve.scheduler_step"]["inflight"] == 0


def test_deadline_resolution_order(monkeypatch):
    mon = liveness.enable(deadline=7.0, start=False)
    liveness.declare_beacon("test.dl_declared", "x", deadline=11.0)
    liveness.declare_beacon("test.dl_bare", "x")
    # declared default beats the monitor/global default
    assert mon.deadline_for("test.dl_declared") == 11.0
    assert mon.deadline_for("test.dl_bare") == 7.0
    # per-beacon env beats everything (dots spelled as underscores)
    monkeypatch.setenv("PADDLE_TPU_LIVENESS_DEADLINE_TEST_DL_DECLARED",
                       "0.5")
    assert mon.deadline_for("test.dl_declared") == 0.5
    # the global env seeds the monitor default at construction
    monkeypatch.setenv("PADDLE_TPU_LIVENESS_DEADLINE", "3.0")
    assert liveness.enable(start=False).deadline_for("test.dl_bare") \
        == 3.0


# ---------------------------------------------------------------------------
# stall detection + the flight dump
# ---------------------------------------------------------------------------

def test_stall_dump_names_beacon_and_embeds_all_thread_stacks(
        monkeypatch, armed):
    rec, mon = armed
    liveness.declare_beacon("test.stall", "suite probe")
    monkeypatch.setenv("PADDLE_TPU_LIVENESS_DEADLINE_TEST_STALL", "0.02")
    b = liveness.beacon("test.stall")
    assert mon.check_now() == []                # idle: unwatched
    with b:
        time.sleep(0.04)
        fired = mon.check_now()
    assert len(fired) == 1
    info = fired[0]
    assert info["beacon"] == "test.stall"
    assert info["age_s"] > 0.02
    doc = json.load(open(info["dump"]))
    trig = doc["trigger"]
    assert trig["kind"] == "stall"
    assert trig["beacon"] == "test.stall"
    assert trig["deadline_s"] == 0.02
    # the faulthandler all-thread dump: this (main) thread's frames and
    # at least one "Thread"/"Current thread" header are in it
    assert "test_liveness.py" in trig["stacks"]
    assert "thread" in trig["stacks"].lower()
    # the stall event itself is in the ring, right before the trigger
    kinds = [ev["kind"] for ev in doc["ring"]]
    assert "stall" in kinds
    # and the catalog'd counter fired with the beacon label
    snap = reg_mod.default_registry().snapshot()
    series = snap["liveness.stalls"]["series"]
    assert any(s["labels"] == {"beacon": "test.stall"} and s["value"] >= 1
               for s in series)


def test_stall_rearms_only_after_progress(monkeypatch, armed):
    _rec, mon = armed
    liveness.declare_beacon("test.rearm", "suite probe")
    monkeypatch.setenv("PADDLE_TPU_LIVENESS_DEADLINE_TEST_REARM", "0.01")
    b = liveness.beacon("test.rearm")
    with b:
        time.sleep(0.03)
        assert len(mon.check_now()) == 1
        assert mon.check_now() == []            # same hang: one dump
        b.pulse()                               # progress...
        time.sleep(0.03)
        assert len(mon.check_now()) == 1        # ...then a NEW stall
    assert mon.check_now() == []                # idle again: unwatched


def test_sibling_completions_cannot_mask_a_wedged_entry(monkeypatch,
                                                        armed):
    """Review regression: beacons are shared per NAME (every TCPStore
    fetches 'store.op'), so the stall clock tracks each outstanding
    entry — a publisher thread's quick ops completing/pulsing on the
    same beacon must not reset the clock of a concurrently wedged op."""
    _rec, mon = armed
    liveness.declare_beacon("test.shared", "suite probe")
    monkeypatch.setenv("PADDLE_TPU_LIVENESS_DEADLINE_TEST_SHARED",
                       "0.05")
    b = liveness.beacon("test.shared")
    wedged = threading.Event()
    release = threading.Event()

    # Every wait below ends on an event, not on the clock: the timeouts
    # only bound a broken run, so a loaded machine cannot fail the test.
    def wedge():
        with b:
            wedged.set()
            release.wait(120.0)

    t = threading.Thread(target=wedge, name="wedged-op")
    t.start()
    try:
        assert wedged.wait(60.0)
        deadline = time.time() + 60.0
        fired = []
        while not fired and time.time() < deadline:
            with b:          # healthy sibling traffic, refreshes last_ns
                pass
            fired = mon.check_now()
            time.sleep(0.005)
        assert fired, "sibling completions masked the wedged entry"
        assert fired[0]["beacon"] == "test.shared"
        assert fired[0]["age_s"] > 0.05
    finally:
        release.set()
        t.join(60.0)
    assert not t.is_alive() and b.inflight == 0


def test_enable_replacement_carries_live_beacons(monkeypatch, armed):
    """Review regression: re-enable() (e.g. to set an exit rc) must not
    orphan beacons components already cached — the carried handle keeps
    being watched by the replacement monitor.  A disable()/enable()
    cycle must carry them too."""
    _rec, _mon = armed
    liveness.declare_beacon("test.carry", "suite probe")
    monkeypatch.setenv("PADDLE_TPU_LIVENESS_DEADLINE_TEST_CARRY", "0.01")
    b = liveness.beacon("test.carry")            # cached pre-replacement
    mon2 = liveness.enable(start=False)          # replace the monitor
    assert liveness.beacon("test.carry") is b    # same object, carried
    assert "test.carry" in liveness.state()
    with b:
        time.sleep(0.03)
        fired = mon2.check_now()
    assert fired and fired[0]["beacon"] == "test.carry"
    # the off/on cycle: the cached handle must still be watched
    liveness.disable()
    mon3 = liveness.enable(start=False)
    assert liveness.beacon("test.carry") is b
    with b:
        time.sleep(0.03)
        fired = mon3.check_now()
    assert fired and fired[0]["beacon"] == "test.carry"


def test_malformed_env_knobs_degrade_loudly_never_raise(monkeypatch,
                                                        capsys):
    """Review regression: typo'd liveness env values must warn and fall
    through, never crash enable()/state()/deadline_for (the /healthz
    handler and every monitor poll read them)."""
    liveness.declare_beacon("test.badenv", "suite probe", deadline=9.0)
    monkeypatch.setenv("PADDLE_TPU_LIVENESS_DEADLINE", "5s")
    monkeypatch.setenv("PADDLE_TPU_LIVENESS_DEADLINE_TEST_BADENV", "5m")
    monkeypatch.setenv("PADDLE_TPU_LIVENESS_EXIT_RC", "seventy")
    mon = liveness.enable(start=False)      # must not raise
    assert mon.default_deadline == liveness.DEADLINE_DEFAULT
    assert mon.exit_rc is None
    # the bad per-beacon override falls through to the declared default
    assert mon.deadline_for("test.badenv") == 9.0
    with liveness.beacon("test.badenv"):
        assert liveness.state()["test.badenv"]["deadline_s"] == 9.0
        mon.check_now()                     # poll survives the bad env
    err = capsys.readouterr().err
    assert "PADDLE_TPU_LIVENESS_DEADLINE ignored" in err
    assert "PADDLE_TPU_LIVENESS_EXIT_RC ignored" in err
    liveness.disable()
    # no monitor: the module-level resolver uses the same chain
    assert liveness.deadline_for("test.badenv") == 9.0


def test_malformed_aggregate_env_knobs_degrade_loudly(monkeypatch,
                                                      capsys):
    """Review regression: typo'd telemetry knobs warn and use the
    default — they must never crash worker startup (publisher) or
    host-0's merge loop / the cluster CLI (straggler pct)."""
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_INTERVAL", "10s")
    monkeypatch.setenv("PADDLE_TPU_STRAGGLER_PCT", "25%")
    assert aggregate.straggler_pct_default() == 25.0
    pub = aggregate.HostPublisher(store=object(), host=0)
    assert pub.interval == 10.0
    merged = aggregate.merge_docs({0: _doc(0, 0.1), 1: _doc(1, 0.4)}, 2)
    assert merged["straggler_pct"] == 25.0
    err = capsys.readouterr().err
    assert "PADDLE_TPU_TELEMETRY_INTERVAL ignored" in err
    assert "PADDLE_TPU_STRAGGLER_PCT ignored" in err


def test_cluster_cli_unreachable_master_exits_2():
    """Review regression: a dead/unreachable store is the exit-2 case
    (nothing fetched), not a traceback and not exit 1 ("some hosts
    missing")."""
    from paddle_tpu.observability.__main__ import main
    rc = main(["cluster", "--master", "127.0.0.1:1", "--world", "2",
               "--timeout", "0.5"])
    assert rc == 2


@pytest.mark.slow
def test_bad_flight_signal_env_does_not_break_import(tmp_path):
    """Review regression: a typo'd PADDLE_TPU_FLIGHT_SIGNAL must degrade
    to a loud stderr warning, never crash `import paddle_tpu`."""
    proc = _run_child("""
        from paddle_tpu.observability import flight
        print("imported")
        """, {"PADDLE_TPU_FLIGHT_SIGNAL": "BOGUS"})
    assert proc.returncode == 0, proc.stderr
    assert "imported" in proc.stdout
    assert "PADDLE_TPU_FLIGHT_SIGNAL ignored" in proc.stderr
    # the explicit API stays strict: unknown names raise for the caller
    with pytest.raises(ValueError, match="unknown signal"):
        flight.install_signal_handler("NOTASIGNAL")


def test_state_readout_shows_stall_without_monitor_poll(monkeypatch):
    """liveness.state() computes 'stalled' on read — the /healthz path
    needs no monitor thread to have polled."""
    liveness.enable(start=False)
    liveness.declare_beacon("test.state", "suite probe")
    monkeypatch.setenv("PADDLE_TPU_LIVENESS_DEADLINE_TEST_STATE", "0.01")
    b = liveness.beacon("test.state")
    with b:
        time.sleep(0.03)
        st = liveness.state()["test.state"]
        assert st["stalled"] and st["inflight"] == 1
        assert st["age_s"] > 0.01 and st["deadline_s"] == 0.01
    assert not liveness.state()["test.state"]["stalled"]


# ---------------------------------------------------------------------------
# injected Hang chaos: the watchdog fires at beacon-covered sites
# ---------------------------------------------------------------------------

def test_hang_chaos_scheduler_step_watchdog_fires_and_engine_survives(
        monkeypatch, tmp_path, gpt_engine):
    """ACCEPTANCE: an injected Hang at a beacon-covered site produces,
    within the deadline, a stall flight dump containing all-thread
    stacks and the stalled beacon name — and the post-hang engine stays
    serviceable (greedy output identical to the unhanged run)."""
    from paddle_tpu.serving.scheduler import Request
    _model, engine = gpt_engine
    sched = _sched(engine)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 100, (8,)) for _ in range(3)]
    for p in prompts:
        sched.submit(Request(prompt=p, max_new_tokens=4, temperature=0.0))
    base = {r.rid: r.tokens.tolist() for r in sched.run().values()}
    # warm run compiled every program; now arm a REAL monitor thread
    # with a tiny deadline and hang the third scheduler iteration
    flight.enable(dir=str(tmp_path))
    monkeypatch.setenv(
        "PADDLE_TPU_LIVENESS_DEADLINE_SERVE_SCHEDULER_STEP", "0.05")
    mon = liveness.enable(poll=0.01)
    from paddle_tpu.serving.scheduler import ContinuousBatchingScheduler
    sched2 = ContinuousBatchingScheduler(engine)
    assert sched2._beacon is not liveness.NOOP_BEACON
    for p in prompts:
        sched2.submit(Request(prompt=p, max_new_tokens=4,
                              temperature=0.0))
    plan = FaultPlan(seed=0).inject("serve.step", Hang(0.3), at=2)
    with chaos(plan):
        out = sched2.run()
    plan.assert_all_fired()
    # post-hang serviceability: the drain completed, greedy identical
    got = {r.rid: r.tokens.tolist() for r in out.values()}
    assert got == base
    # the monitor (its own thread) fired DURING the hang
    stalls = [s for s in mon.stall_log
              if s["beacon"] == "serve.scheduler_step"]
    assert stalls, mon.stall_log
    doc = json.load(open(stalls[-1]["dump"]))
    assert doc["trigger"]["beacon"] == "serve.scheduler_step"
    assert "run" in doc["trigger"]["stacks"]     # the wedged frames
    assert engine.decode_compile_count == 1      # nothing retraced


@pytest.mark.slow
def test_hang_chaos_checkpoint_write_watchdog_fires(monkeypatch,
                                                    tmp_path):
    """A wedged (injected-Hang) checkpoint shard write stalls the
    checkpoint.writer beacon on the WRITER thread; the monitor fires
    from the test thread and the save still completes after the hang.
    (slow: runs in the unfiltered CI observability job — the tier-1
    hang acceptance is the scheduler-step scenario above.)"""
    from paddle_tpu.incubate.checkpoint import CheckpointManager
    flight.enable(dir=str(tmp_path / "flight"))
    monkeypatch.setenv("PADDLE_TPU_LIVENESS_DEADLINE_CHECKPOINT_WRITER",
                       "0.05")
    mon = liveness.enable(start=False)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    plan = FaultPlan(seed=0).inject("checkpoint.shard_write", Hang(0.3),
                                    at=0)
    with chaos(plan):
        mgr.save(1, {"w": np.ones((8,), np.float32)})   # async writer
        deadline = time.time() + 5.0
        fired = []
        while not fired and time.time() < deadline:
            fired = mon.check_now()
            time.sleep(0.01)
    plan.assert_all_fired()
    mgr.close()
    assert fired and fired[0]["beacon"] == "checkpoint.writer"
    doc = json.load(open(fired[0]["dump"]))
    assert doc["trigger"]["beacon"] == "checkpoint.writer"
    assert "_write" in doc["trigger"]["stacks"]
    # post-hang: the save landed and restores
    restored = CheckpointManager(str(tmp_path / "ckpt")).restore()
    assert np.allclose(np.asarray(restored["w"]), 1.0)


def test_hang_action_composes_with_plan_schedules():
    from paddle_tpu.robustness.faultpoints import declare, faultpoint
    declare("test.hang_site", "suite probe")
    plan = FaultPlan(seed=0).inject("test.hang_site", Hang(0.05), at=1)
    with chaos(plan):
        t0 = time.perf_counter()
        faultpoint("test.hang_site")             # hit 0: no hang
        fast = time.perf_counter() - t0
        t0 = time.perf_counter()
        faultpoint("test.hang_site")             # hit 1: sleeps
        slow = time.perf_counter() - t0
    plan.assert_all_fired()
    assert slow >= 0.05 > fast
    assert repr(Hang(1.5)) == "Hang(1.5s)"


# ---------------------------------------------------------------------------
# uncaught worker-thread exceptions -> flight (threading.excepthook)
# ---------------------------------------------------------------------------

def test_uncaught_thread_exception_routes_to_flight(armed, monkeypatch):
    rec, _mon = armed
    # flight chains threading.excepthook at import, but pytest's
    # threadexception plugin swaps in its own hook per test — reinstate
    # ours for the scope (with a recording sentinel as the "previous"
    # hook, so the chain-through is directly asserted)
    chained = []
    monkeypatch.setattr(flight, "_PREV_THREAD_EXCEPTHOOK",
                        chained.append)
    monkeypatch.setattr(threading, "excepthook",
                        flight._thread_excepthook)

    def die():
        raise ZeroDivisionError("injected thread death")

    t = threading.Thread(target=die, name="doomed-worker")
    t.start()
    t.join()
    path = flight.last_dump_path()
    assert path, "no flight dump for the dead thread"
    doc = json.load(open(path))
    assert doc["trigger"]["kind"] == "thread_exception"
    assert doc["trigger"]["thread"] == "doomed-worker"
    assert "ZeroDivisionError" in doc["trigger"]["error"]
    assert "die" in doc["trigger"]["traceback"]    # the unwound frames
    assert "File" in doc["trigger"]["stacks"]      # the other threads
    # the previous hook still ran AFTER the dump (never swallowed)
    assert chained and chained[0].exc_type is ZeroDivisionError


def test_thread_excepthook_is_noop_when_flight_disarmed(monkeypatch):
    assert flight.active() is None
    monkeypatch.setattr(threading, "excepthook",
                        flight._thread_excepthook)

    def die():
        raise RuntimeError("no recorder")

    t = threading.Thread(target=die, name="quiet-death")
    t.start()
    t.join()
    assert flight.last_dump_path() is None


# ---------------------------------------------------------------------------
# subprocess scenarios: hard-exit rc + SIGQUIT postmortem
# ---------------------------------------------------------------------------

def _run_child(code, env_extra, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "-c",
                           textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=timeout)


@pytest.mark.slow
def test_stall_hard_exit_rc_for_launcher_respawn(tmp_path):
    """PADDLE_TPU_LIVENESS_EXIT_RC: a stall hard-exits with the
    configured rc, so the elastic launcher treats the hung worker as a
    restartable crash (its normal restart-budget rules apply)."""
    proc = _run_child("""
        import time
        from paddle_tpu.observability import liveness
        liveness.declare_beacon("test.exit", "child probe")
        b = liveness.beacon("test.exit")
        with b:
            time.sleep(60)          # wedged: the monitor must kill us
        """, {
        "PADDLE_TPU_LIVENESS": "1",
        "PADDLE_TPU_LIVENESS_DEADLINE": "0.2",
        "PADDLE_TPU_LIVENESS_POLL": "0.05",
        "PADDLE_TPU_LIVENESS_EXIT_RC": "77",
        "PADDLE_TPU_FLIGHT": "1",
        "PADDLE_TPU_FLIGHT_DIR": str(tmp_path),
    })
    assert proc.returncode == 77, (proc.returncode, proc.stderr)
    assert "STALL" in proc.stderr and "test.exit" in proc.stderr
    dumps = list(tmp_path.glob("flight-*.json"))
    assert dumps, "hard exit must still leave the stall dump"
    doc = json.load(open(dumps[0]))
    assert doc["trigger"]["kind"] == "stall"
    assert doc["trigger"]["beacon"] == "test.exit"


@pytest.mark.slow
def test_sigquit_manual_postmortem_subprocess(tmp_path):
    """PADDLE_TPU_FLIGHT_SIGNAL=SIGQUIT: the operator pokes a live
    process and gets all-thread stacks on stderr + a flight ring dump,
    WITHOUT killing it (the child exits 0 on its own)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_FLIGHT="1",
               PADDLE_TPU_FLIGHT_DIR=str(tmp_path),
               PADDLE_TPU_FLIGHT_SIGNAL="SIGQUIT")
    code = textwrap.dedent("""
        import sys, time
        from paddle_tpu.observability import flight
        print("ready", flush=True)
        deadline = time.time() + 60
        while time.time() < deadline:
            if flight.last_dump_path():
                sys.exit(0)        # dump observed: clean exit
            time.sleep(0.05)
        sys.exit(3)
        """)
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            cwd=REPO, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "ready"
        proc.send_signal(signal.SIGQUIT)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    _out, err = proc.communicate()
    assert rc == 0, (rc, err)
    assert "SIGQUIT" in err and "Current thread" in err
    dumps = list(tmp_path.glob("flight-*.json"))
    assert dumps
    doc = json.load(open(dumps[0]))
    assert doc["trigger"]["kind"] == "signal"
    assert doc["trigger"]["signal"] == "SIGQUIT"
    assert "File" in doc["trigger"]["stacks"]


# ---------------------------------------------------------------------------
# aggregation: per-host publish -> host-0 merge -> straggler detection
# ---------------------------------------------------------------------------

def _doc(host, p50, count=10, ts=None, stalled=(), fmt=None):
    return {
        "format": fmt or "paddle_tpu-telemetry-v1",
        "host": host, "pid": 1,
        "wall_ts": time.time() if ts is None else ts,
        "beacons": {n: {"count": 1, "inflight": 1, "age_s": 9.9,
                        "deadline_s": 1.0, "stalled": True}
                    for n in stalled},
        "step_times": ({"train.step_seconds": {
            "count": count, "sum": p50 * count, "p50": p50,
            "p95": p50 * 1.1, "p99": p50 * 1.2}} if p50 is not None
            else {}),
        "stalls": {}, "metrics": {},
    }


def test_merge_docs_straggler_rule_and_gauge():
    docs = {0: _doc(0, 0.10), 1: _doc(1, 0.11), 2: _doc(2, 0.30)}
    merged = aggregate.merge_docs(docs, 4, pct=25.0)
    assert merged["stragglers"] == [2]
    assert merged["missing"] == [3]
    assert merged["hosts"][2]["straggler"]
    assert not merged["hosts"][0]["straggler"]
    assert merged["median_step_s"] == 0.11
    # the catalog'd gauge is set per published host (1 flagged / 0 not)
    snap = reg_mod.default_registry().snapshot()
    series = {s["labels"]["host"]: s["value"]
              for s in snap["liveness.straggler"]["series"]}
    assert series["2"] == 1.0 and series["0"] == 0.0
    # a 25%-threshold boundary host is NOT flagged (strictly over)
    merged = aggregate.merge_docs(
        {0: _doc(0, 0.10), 1: _doc(1, 0.125)}, 2, pct=25.0)
    assert merged["stragglers"] == []


def test_merge_docs_needs_two_paced_hosts_and_tolerates_paceless():
    # a single host can never be its own straggler
    merged = aggregate.merge_docs({0: _doc(0, 0.5)}, 1)
    assert merged["stragglers"] == []
    # hosts without step samples join the table but not the median
    merged = aggregate.merge_docs(
        {0: _doc(0, 0.1), 1: _doc(1, 0.3), 2: _doc(2, None)}, 3)
    assert merged["stragglers"] == [1]
    assert merged["hosts"][2]["step_metric"] is None
    # stalled beacons ride into the merged row
    merged = aggregate.merge_docs(
        {0: _doc(0, 0.1, stalled=("serve.scheduler_step",))}, 1)
    assert merged["hosts"][0]["stalled_beacons"] == \
        ["serve.scheduler_step"]
    txt = aggregate.format_cluster(merged)
    assert "STALLED" in txt and "serve.scheduler_step" in txt


def test_host_snapshot_and_publisher_store_roundtrip():
    from paddle_tpu.distributed.store import TCPStore
    reg_mod.default_registry().histogram(
        "train.step_seconds").observe(0.123)
    liveness.enable(start=False)
    liveness.declare_beacon("test.pub", "suite probe")
    with liveness.beacon("test.pub"):
        doc = aggregate.host_snapshot(0)
    assert doc["format"] == "paddle_tpu-telemetry-v1"
    assert doc["step_times"]["train.step_seconds"]["count"] >= 1
    assert doc["beacons"]["test.pub"]["inflight"] == 1
    assert "train.step_seconds" in doc["metrics"]
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    pub = aggregate.HostPublisher(TCPStore("127.0.0.1", master.port),
                                  host=0, interval=999.0)
    key = pub.publish_once()
    assert key == aggregate.KEY_PREFIX + "0"
    docs, missing = aggregate.fetch_cluster(
        TCPStore("127.0.0.1", master.port), 2)
    assert list(docs) == [0] and missing == [1]
    assert docs[0]["host"] == 0


def test_publisher_thread_loop_and_final_publish():
    from paddle_tpu.distributed.store import TCPStore
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    pub = aggregate.HostPublisher(TCPStore("127.0.0.1", master.port),
                                  host=3, interval=0.02)
    pub.start()
    deadline = time.time() + 5.0
    while pub.published < 2 and time.time() < deadline:
        time.sleep(0.01)
    pub.stop()                       # also publishes the exit snapshot
    assert pub.published >= 3
    docs, _ = aggregate.fetch_cluster(
        TCPStore("127.0.0.1", master.port), 4)
    assert 3 in docs


class _WedgedStore:
    """store.set sleeps long enough to wedge the publisher loop inside
    it; counts concurrent set() calls to catch the stop-final race."""

    def __init__(self, delay):
        self.delay = delay
        self.active = 0
        self.max_active = 0
        self._lock = threading.Lock()

    def set(self, key, value):
        with self._lock:
            self.active += 1
            self.max_active = max(self.max_active, self.active)
        time.sleep(self.delay)
        with self._lock:
            self.active -= 1


def test_publisher_stop_bounded_and_final_never_races_wedged_loop():
    """Regression (TPU603/tpu-race introduction): stop() on a publisher
    wedged inside a store op must stay bounded AND must not fire the
    final publish concurrently with the wedged one — two unsynchronized
    set()s on the same key published a torn/stale exit snapshot, and
    `published` was bumped from two threads without a lock."""
    store = _WedgedStore(delay=0.6)
    pub = aggregate.HostPublisher(store, host=0, interval=0.01).start()
    deadline = time.time() + 5.0
    while store.active == 0 and time.time() < deadline:
        time.sleep(0.005)            # loop thread is now inside set()
    assert store.active == 1
    t0 = time.time()
    pub.stop(timeout=0.05, final=True)
    assert time.time() - t0 < 0.5    # bounded: join timeout honored
    assert store.max_active == 1     # final publish skipped, no overlap


def test_cluster_cli_exit_code_discipline():
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.observability.__main__ import main
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=2)
    addr = "127.0.0.1:%d" % master.port
    # nobody published: exit 2, never silent green
    assert main(["cluster", "--master", addr, "--world", "2"]) == 2
    client = TCPStore("127.0.0.1", master.port)
    client.set(aggregate.KEY_PREFIX + "0",
               json.dumps(_doc(0, 0.1)).encode())
    # partial publication: exit 1
    assert main(["cluster", "--master", addr, "--world", "2"]) == 1
    client.set(aggregate.KEY_PREFIX + "1",
               json.dumps(_doc(1, 0.3)).encode())
    # complete: exit 0 (both formats)
    assert main(["cluster", "--master", addr, "--world", "2"]) == 0
    assert main(["cluster", "--master", addr, "--world", "2",
                 "--format", "json"]) == 0
    # malformed --master / missing master: exit 2
    assert main(["cluster", "--world", "2", "--master", ""]) == 2
    assert main(["cluster", "--world", "2", "--master", "nocolon"]) == 2


def test_cluster_cli_renders_straggler_table(capsys):
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.observability.__main__ import main
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=2)
    client = TCPStore("127.0.0.1", master.port)
    client.set(aggregate.KEY_PREFIX + "0",
               json.dumps(_doc(0, 0.1)).encode())
    client.set(aggregate.KEY_PREFIX + "1",
               json.dumps(_doc(1, 0.4)).encode())
    rc = main(["cluster", "--master", "127.0.0.1:%d" % master.port,
               "--world", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "STRAGGLER" in out and "median step" in out


@pytest.mark.slow
def test_two_process_store_backed_aggregation_smoke(tmp_path):
    """The CI smoke: two real worker PROCESSES publish through one
    store master; the ``cluster`` CLI (a third process) merges them
    with a non-empty straggler table and a hard rc."""
    from paddle_tpu.distributed.store import TCPStore
    master = TCPStore("127.0.0.1", 0, is_master=True, world_size=2)
    code = """
        import sys
        from paddle_tpu.distributed.store import TCPStore
        from paddle_tpu.observability import aggregate, registry
        host, port = int(sys.argv[1]), int(sys.argv[2])
        h = registry.default_registry().histogram("train.step_seconds")
        for _ in range(12):
            h.observe(0.1 if host == 0 else 0.4)   # host 1 lags 4x
        store = TCPStore("127.0.0.1", port)
        aggregate.HostPublisher(store, host=host,
                                interval=999.0).publish_once()
        """
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", textwrap.dedent(code), str(h),
         str(master.port)], env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for h in (0, 1)]
    for p in procs:
        _out, err = p.communicate(timeout=180)
        assert p.returncode == 0, err.decode()
    cli = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability", "cluster",
         "--master", "127.0.0.1:%d" % master.port, "--world", "2"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=180)
    assert cli.returncode == 0, cli.stderr
    assert "STRAGGLER" in cli.stdout, cli.stdout
    js = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability", "cluster",
         "--master", "127.0.0.1:%d" % master.port, "--world", "2",
         "--format", "json"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=180)
    assert js.returncode == 0
    doc = json.loads(js.stdout)
    assert doc["stragglers"] == [1]
    assert doc["missing"] == []
