"""Chaos suite: every injected fault is either RECOVERED (retry / fallback
restore / rewind / emergency checkpoint, asserted on the resulting state)
or surfaced as a LOUD TYPED error — never a silent partial checkpoint,
dropped save, or hung wait.  And with no FaultPlan active, every
instrumented faultpoint is a no-op (asserted) so tier-1 behavior is
unchanged.

Layers under test: paddle_tpu.robustness (faultpoints/retry/preemption/
sentinel), incubate.checkpoint (manifests, fallback, atexit flush),
distributed.store (retrying client ops, backoff wait/barrier),
distributed.launch_main (crash-loop backoff, preempted rc), jit.TrainStep +
amp.GradScaler instrumentation.
"""
import errno
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import robustness as rb
from paddle_tpu.incubate.checkpoint import (
    CheckpointCorruptionError, CheckpointFallbackWarning, CheckpointManager,
    CheckpointWriteError, NoUsableCheckpointError, TrainEpochRange)
from paddle_tpu.jit import TrainStep
from paddle_tpu.robustness import faultpoints as fp
from paddle_tpu.robustness.preemption import PREEMPTED_RC, PreemptionGuard
from paddle_tpu.robustness.retry import (RetryError, backoff_delays,
                                         retry_call, transient)
from paddle_tpu.robustness.sentinel import (DivergenceError,
                                            DivergenceSentinel)

REQUIRED_SITES = {
    "checkpoint.shard_write", "checkpoint.shard_file", "checkpoint.publish",
    "checkpoint.restore_read", "train.epoch", "train.grads",
    "amp.found_inf", "store.client_op", "launch.respawn",
    "serve.replica",
}


def _tiny_step(seed=7, lr=0.05):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 1))
    opt = paddle.optimizer.SGD(learning_rate=lr, parameters=net.parameters())
    return TrainStep(net, nn.functional.mse_loss, opt)


def _data(n=10, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(8, 4).astype("float32"),
             rng.randn(8, 1).astype("float32")) for _ in range(n)]


# ==========================================================================
# faultpoints framework
# ==========================================================================

def test_registry_covers_instrumented_stack():
    # the modules register their sites at import; all are imported above
    # (store/launch via paddle_tpu.distributed)
    import paddle_tpu.distributed.launch_main  # noqa: F401
    import paddle_tpu.distributed.store  # noqa: F401
    import paddle_tpu.serving.router  # noqa: F401
    assert REQUIRED_SITES <= set(fp.SITES), \
        REQUIRED_SITES - set(fp.SITES)


def test_faultpoint_is_noop_without_plan():
    assert fp.active_plan() is None
    assert fp.faultpoint("checkpoint.shard_write", path="/nope") is None
    # and instrumented production paths behave normally (no counting, no
    # mutation): a full save/restore round-trip with no plan installed
    # is byte-identical behavior to the pre-chaos code
    plan = rb.FaultPlan()
    assert plan.hits("checkpoint.shard_write") == 0


def test_faultplan_deterministic_schedules():
    fp.declare("test.site", "test-local site")

    def run(seed):
        plan = rb.FaultPlan(seed=seed)
        plan.inject("test.site", fp.Raise(ValueError("boom")), prob=0.4,
                    times=4)
        fired = []
        with rb.chaos(plan):
            for i in range(24):
                try:
                    fp.faultpoint("test.site")
                except ValueError:
                    fired.append(i)
        return fired

    a, b, c = run(5), run(5), run(6)
    assert a == b                      # seeded: reproducible
    assert 0 < len(a) <= 4             # times= cap respected
    assert a != c                      # different seed, different schedule


def test_faultplan_at_every_first_n():
    fp.declare("test.sched", "test-local site")
    plan = rb.FaultPlan()
    plan.inject("test.sched", fp.Raise(KeyError("k")), at=2)
    fired = []
    with rb.chaos(plan):
        for i in range(5):
            try:
                fp.faultpoint("test.sched")
            except KeyError:
                fired.append(i)
    assert fired == [2]
    assert plan.hits("test.sched") == 5
    assert plan.fired_at("test.sched") == [2]
    plan.assert_all_fired()

    plan2 = rb.FaultPlan()
    plan2.inject("test.sched", fp.Raise(KeyError("k")), every=3)
    fired2 = []
    with rb.chaos(plan2):
        for i in range(7):
            try:
                fp.faultpoint("test.sched")
            except KeyError:
                fired2.append(i)
    assert fired2 == [0, 3, 6]


def test_faultplan_rejects_unknown_site_and_unfired_asserts():
    plan = rb.FaultPlan()
    with pytest.raises(ValueError, match="unknown faultpoint site"):
        plan.inject("no.such.site", fp.DiskFull())
    fp.declare("test.unreached", "never hit")
    plan.inject("test.unreached", fp.DiskFull(), at=0)
    with pytest.raises(AssertionError, match="never fired"):
        plan.assert_all_fired()


def test_nested_chaos_rejected():
    with rb.chaos(rb.FaultPlan()):
        with pytest.raises(RuntimeError, match="nested"):
            with rb.chaos(rb.FaultPlan()):
                pass
    assert fp.active_plan() is None


# ==========================================================================
# retry
# ==========================================================================

def test_retry_succeeds_after_transient_failures():
    calls = {"n": 0}
    sleeps = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 4:
            raise ConnectionResetError("nope")
        return "ok"

    out = retry_call(flaky, tries=6, base_delay=0.01, jitter=0.0,
                     sleep=sleeps.append)
    assert out == "ok" and calls["n"] == 4
    assert sleeps == [0.01, 0.02, 0.04]  # exponential, jitter disabled


def test_retry_exhaustion_raises_typed_error():
    def always():
        raise ConnectionResetError("still down")

    with pytest.raises(RetryError) as ei:
        retry_call(always, tries=3, base_delay=0.001, sleep=lambda d: None)
    assert ei.value.attempts == 3
    assert isinstance(ei.value.last_error, ConnectionResetError)
    assert isinstance(ei.value.__cause__, ConnectionResetError)


def test_retry_nontransient_fails_fast():
    calls = {"n": 0}

    def enospc():
        calls["n"] += 1
        raise OSError(errno.ENOSPC, "No space left on device")

    # ENOSPC is NOT transient: no retries, original error propagates
    with pytest.raises(OSError) as ei:
        retry_call(enospc, tries=5, sleep=lambda d: None)
    assert calls["n"] == 1 and ei.value.errno == errno.ENOSPC
    assert not transient(ei.value)
    assert transient(ConnectionResetError())
    assert transient(OSError(errno.ETIMEDOUT, "t"))


def test_retry_deadline_bounds_total_time():
    t = {"now": 0.0}
    sleeps = []

    def fake_sleep(d):
        sleeps.append(d)
        t["now"] += d

    def always():
        raise ConnectionError("down")

    import paddle_tpu.robustness.retry as retry_mod
    real = retry_mod.time.monotonic
    retry_mod.time.monotonic = lambda: t["now"]
    try:
        with pytest.raises(RetryError) as ei:
            retry_call(always, tries=1000, base_delay=0.5, jitter=0.0,
                       deadline=2.0, sleep=fake_sleep)
    finally:
        retry_mod.time.monotonic = real
    assert ei.value.elapsed >= 2.0
    assert len(sleeps) < 10  # deadline, not tries, ended it


def test_backoff_delays_jitter_seeded():
    import random
    a = list(next(backoff_delays(0.1, jitter=0.5, rng=random.Random(3)))
             for _ in range(1))
    b = list(next(backoff_delays(0.1, jitter=0.5, rng=random.Random(3)))
             for _ in range(1))
    assert a == b
    d = backoff_delays(0.1, cap=0.4, jitter=0.0)
    assert [next(d) for _ in range(4)] == [0.1, 0.2, 0.4, 0.4]


# ==========================================================================
# checkpoint: integrity, fallback, no silent partials
# ==========================================================================

def test_manifest_written_and_matches(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"v": np.arange(4.0)})
    d = os.path.join(str(tmp_path), "ckpt-1")
    with open(os.path.join(d, "host-0.manifest.json")) as f:
        man = json.load(f)
    import hashlib
    blob = open(os.path.join(d, "host-0.ckpt"), "rb").read()
    assert man["nbytes"] == len(blob)
    assert man["sha256"] == hashlib.sha256(blob).hexdigest()
    out = mgr.restore()
    np.testing.assert_array_equal(out["v"], np.arange(4.0))


def test_enospc_sync_save_publishes_nothing(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"v": 1})
    plan = rb.FaultPlan().inject("checkpoint.shard_write", fp.DiskFull())
    with rb.chaos(plan):
        with pytest.raises(OSError) as ei:
            mgr.save(2, {"v": 2})
    assert ei.value.errno == errno.ENOSPC
    plan.assert_all_fired()
    # no DONE-published partial: step 2 is not eligible, step 1 intact
    assert mgr.all_steps() == [1]
    assert mgr.restore()["v"] == 1
    mgr.save(3, {"v": 3})  # manager still usable after the failure
    assert mgr.latest_step() == 3


def test_enospc_async_surfaces_on_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    plan = rb.FaultPlan().inject("checkpoint.shard_write", fp.DiskFull())
    with rb.chaos(plan):
        mgr.save(5, {"v": 5})
        with pytest.raises(RuntimeError, match="async checkpoint failed"):
            mgr.wait()
    plan.assert_all_fired()
    assert mgr.all_steps() == []  # nothing silently half-published


def test_torn_shard_write_is_never_published(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    plan = rb.FaultPlan().inject("checkpoint.shard_file",
                                 fp.TornFile(frac=0.25))
    with rb.chaos(plan):
        with pytest.raises(CheckpointWriteError, match="torn shard"):
            mgr.save(1, {"v": np.arange(64.0)})
    plan.assert_all_fired()
    assert mgr.all_steps() == []
    assert not os.path.exists(os.path.join(str(tmp_path), "ckpt-1", "DONE"))


def test_corrupt_newest_restore_falls_back(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"v": np.full((4,), 1.0)})
    mgr.save(2, {"v": np.full((4,), 2.0)})
    # bit-rot the newest published shard
    shard = os.path.join(str(tmp_path), "ckpt-2", "host-0.ckpt")
    blob = bytearray(open(shard, "rb").read())
    blob[len(blob) // 2] ^= 0x40
    open(shard, "wb").write(bytes(blob))
    with pytest.warns(CheckpointFallbackWarning, match="ckpt-2.*unusable"):
        out = mgr.restore()
    np.testing.assert_array_equal(out["v"], np.full((4,), 1.0))
    # naming the bad step explicitly still fails loud and typed
    with pytest.raises(CheckpointCorruptionError, match="sha256"):
        mgr.restore(step=2)


def test_truncated_newest_restore_falls_back(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"v": 1.0})
    mgr.save(2, {"v": 2.0})
    shard = os.path.join(str(tmp_path), "ckpt-2", "host-0.ckpt")
    os.truncate(shard, os.path.getsize(shard) // 2)
    with pytest.warns(CheckpointFallbackWarning):
        assert mgr.restore()["v"] == 1.0
    with pytest.raises(CheckpointCorruptionError, match="torn"):
        mgr.restore(step=2)


def test_unpicklable_newest_restore_falls_back(tmp_path):
    import hashlib
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"v": 1.0})
    mgr.save(2, {"v": 2.0})
    d = os.path.join(str(tmp_path), "ckpt-2")
    garbage = b"not a pickle at all"
    open(os.path.join(d, "host-0.ckpt"), "wb").write(garbage)
    # manifest agrees with the garbage: integrity passes, unpickling fails
    with open(os.path.join(d, "host-0.manifest.json"), "w") as f:
        json.dump({"sha256": hashlib.sha256(garbage).hexdigest(),
                   "nbytes": len(garbage), "host": 0, "step": 2}, f)
    with pytest.warns(CheckpointFallbackWarning, match="unpicklable"):
        assert mgr.restore()["v"] == 1.0


def test_every_checkpoint_bad_raises_typed(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"v": 1.0})
    mgr.save(2, {"v": 2.0})
    for s in (1, 2):
        shard = os.path.join(str(tmp_path), f"ckpt-{s}", "host-0.ckpt")
        os.truncate(shard, 3)
    with pytest.warns(CheckpointFallbackWarning):
        with pytest.raises(NoUsableCheckpointError, match="every candidate"):
            mgr.restore()
    # empty directory keeps the (FileNotFoundError-compatible) contract
    mgr2 = CheckpointManager(str(tmp_path / "empty"), async_save=False)
    with pytest.raises(FileNotFoundError):
        mgr2.restore()


def test_restore_read_faultpoint_bitflip_detected(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"v": np.arange(32.0)})
    mgr.save(2, {"v": np.arange(32.0) * 2})
    plan = rb.FaultPlan(seed=9).inject("checkpoint.restore_read",
                                       fp.BitFlip(), at=0)
    with rb.chaos(plan):
        with pytest.warns(CheckpointFallbackWarning):
            out = mgr.restore()
    plan.assert_all_fired()
    # newest was corrupted in-flight; older one restored
    np.testing.assert_array_equal(out["v"], np.arange(32.0))


def test_close_flushes_and_rejects_further_saves(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(1, {"v": 1.0})
    mgr.close()
    assert mgr.all_steps() == [1]
    with pytest.raises(RuntimeError, match="closed"):
        mgr.save(2, {"v": 2.0})
    mgr.close()  # idempotent


@pytest.mark.slow
def test_atexit_flushes_queued_async_saves(tmp_path):
    """The satellite bug: a daemon writer thread dies with the interpreter,
    silently dropping queued saves.  A subprocess that exits IMMEDIATELY
    after an async save() must still land the checkpoint."""
    script = textwrap.dedent("""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        import numpy as np
        from paddle_tpu.incubate.checkpoint import CheckpointManager
        mgr = CheckpointManager(sys.argv[1], async_save=True)
        mgr.save(4, {"v": np.arange(1024.0)})
        # NO wait(), NO close(): straight to interpreter exit
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    ck = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", script, ck],
                       capture_output=True, text=True, timeout=600,
                       cwd="/root/repo", env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    mgr = CheckpointManager(ck, async_save=False)
    assert mgr.all_steps() == [4], os.listdir(ck)
    np.testing.assert_array_equal(mgr.restore()["v"], np.arange(1024.0))


# ==========================================================================
# store: retry, wait/barrier backoff + env timeout
# ==========================================================================

@pytest.fixture
def py_store(monkeypatch):
    """A TCPStore forced onto the pure-Python client/server (the native lib
    bypasses the reconnect path the chaos faults exercise)."""
    from paddle_tpu.distributed import store as store_mod
    monkeypatch.setattr(store_mod._native, "load", lambda: None)
    s = store_mod.TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    return s


def test_store_op_succeeds_after_injected_socket_resets(py_store):
    plan = rb.FaultPlan().inject("store.client_op", fp.SocketReset(),
                                 first_n=3)
    with rb.chaos(plan):
        py_store.set("k", b"v")        # survives 3 consecutive resets
    assert plan.hits("store.client_op") >= 4
    plan.assert_all_fired()
    assert py_store.get("k") == b"v"
    # add after resets: counter still correct (faults fire pre-send)
    plan2 = rb.FaultPlan().inject("store.client_op", fp.SocketReset(),
                                  first_n=2)
    with rb.chaos(plan2):
        assert py_store.add("cnt", 5) == 5
    assert py_store.add("cnt", 0) == 5


def test_store_op_exhaustion_is_typed(py_store, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_RETRY_TRIES", "3")
    monkeypatch.setenv("PADDLE_TPU_RETRY_BASE_DELAY", "0.001")
    plan = rb.FaultPlan().inject("store.client_op", fp.SocketReset(),
                                 every=1)
    with rb.chaos(plan):
        with pytest.raises(RetryError, match="TCPStore.set"):
            py_store.set("k2", b"v")
    assert plan.hits("store.client_op") == 3


def test_store_add_lost_reply_is_typed_not_reissued(py_store):
    """A failure AFTER add's request hit the wire must not be blindly
    retried (the server may have applied it — a reissue double-increments
    and desynchronizes barrier's generation math): it surfaces as
    StoreReplyLostError instead."""
    from paddle_tpu.distributed.store import StoreReplyLostError
    assert py_store.add("exact", 1) == 1
    client = py_store._client
    orig = client._read_full

    def broken_read(n):
        client._read_full = orig       # heal after one failure
        raise ConnectionResetError("reply lost (simulated)")

    client._read_full = broken_read
    with pytest.raises(StoreReplyLostError, match="may or may not"):
        py_store.add("exact", 1)
    # the server DID apply that increment; no hidden duplicate happened
    assert py_store.add("exact", 0) == 2


def test_divergence_monitor_survives_pre_snapshot_divergence():
    """NaN before the first snapshot: the ring is empty — the callback
    must stop training, not crash fit() with DivergenceError."""
    from paddle_tpu.callbacks import DivergenceMonitor

    cb = DivergenceMonitor(snapshot_every=10)

    class FakeModel:
        _train_step = _StubStep()
        stop_training = False

    cb.set_model(FakeModel)
    cb.on_train_batch_end(0, {"loss": float("nan")})  # no snapshot yet
    assert FakeModel.stop_training and cb.rewinds == 0


def test_store_reconnect_after_real_socket_death(py_store):
    """Break the client's stream out from under it: the retry layer
    reconnects and the op still succeeds (real break, not injected).
    shutdown (not close) so the next send raises EPIPE/ECONNRESET — the
    transient class — rather than EBADF."""
    import socket as socket_mod
    py_store.set("alive", b"1")
    py_store._client._sock.shutdown(socket_mod.SHUT_RDWR)
    assert py_store.get("alive") == b"1"


def test_store_wait_timeout_names_missing_keys(py_store):
    py_store.set("present", b"1")
    t0 = time.monotonic()
    with pytest.raises(TimeoutError) as ei:
        py_store.wait(["present", "ghost1", "ghost2"], timeout=0.3)
    msg = str(ei.value)
    # names exactly the keys still missing (the satisfied one only appears
    # in the full requested list)
    assert "missing: ['ghost1', 'ghost2']" in msg
    assert "PADDLE_TPU_STORE_TIMEOUT" in msg
    assert time.monotonic() - t0 < 5.0


def test_store_wait_env_override(py_store, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_STORE_TIMEOUT", "0.2")
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="0.2s"):
        py_store.wait("never-set")     # no per-call timeout: env rules
    assert time.monotonic() - t0 < 5.0


def test_store_barrier_timeout_names_key_and_counts(py_store, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_STORE_TIMEOUT", "0.3")
    py_store.world_size = 2            # we are the only arrival
    t0 = time.monotonic()
    with pytest.raises(TimeoutError) as ei:
        py_store.barrier("sync")       # fixed 60s default is overridden
    assert time.monotonic() - t0 < 5.0
    msg = str(ei.value)
    assert "sync:gen1" in msg and "1 arrival" in msg and "needs 2" in msg


def test_store_barrier_still_meets(py_store):
    py_store.world_size = 1
    py_store.barrier("ok", timeout=5.0)  # single participant: immediate


# ==========================================================================
# launcher: crash-loop backoff + preempted rc
# ==========================================================================

def _launcher(tmp_path, **kw):
    from paddle_tpu.distributed.launch_main import Launcher
    kw.setdefault("log_dir", os.path.join(str(tmp_path), "log"))
    return Launcher(**kw)


def test_launcher_crash_loop_backoff_doubles(tmp_path):
    script = os.path.join(str(tmp_path), "crash.py")
    with open(script, "w") as f:
        f.write("import sys; sys.exit(3)\n")
    launcher = _launcher(tmp_path, nproc_per_node=1, elastic=True,
                         max_restarts=3, restart_delay=0.05,
                         healthy_interval=100.0, poll_interval=0.02)
    rc = launcher.run([sys.executable, script])
    assert rc == 3                     # budget exhausted -> rc propagates
    # one backoff delay per restart, doubling each time (deadline-based:
    # supervision keeps polling while the dead worker waits it out)
    assert launcher.backoff_log == [0.05, 0.1, 0.2]
    assert launcher._restarts[0] == 3


def test_launcher_backoff_resets_after_healthy_uptime(tmp_path):
    script = os.path.join(str(tmp_path), "crash2.py")
    with open(script, "w") as f:
        f.write("import sys; sys.exit(3)\n")
    # healthy_interval=0: every uptime counts as healthy, so the delay
    # never doubles — each respawn sleeps the base delay
    launcher = _launcher(tmp_path, nproc_per_node=1, elastic=True,
                         max_restarts=3, restart_delay=0.05,
                         healthy_interval=0.0, poll_interval=0.02)
    assert launcher.run([sys.executable, script]) == 3
    assert launcher.backoff_log == [0.05, 0.05, 0.05]


def test_launcher_preempted_rc_restart_without_budget(tmp_path):
    """A worker exiting PREEMPTED_RC is restarted even with max_restarts=0
    (it is not a crash) and the job completes cleanly on the retry."""
    script = os.path.join(str(tmp_path), "preempt_once.py")
    marker = os.path.join(str(tmp_path), "ran.marker")
    with open(script, "w") as f:
        f.write(textwrap.dedent(f"""
            import os, sys
            marker = {marker!r}
            if not os.path.exists(marker):
                open(marker, "w").close()
                sys.exit({PREEMPTED_RC})
            sys.exit(0)
        """))
    launcher = _launcher(tmp_path, nproc_per_node=1, elastic=True,
                         max_restarts=0, restart_delay=0.05,
                         poll_interval=0.02)
    assert launcher.run([sys.executable, script]) == 0
    assert launcher._restarts[0] == 0      # no crash budget consumed
    assert launcher.backoff_log == []      # no crash backoff either
    assert launcher.preempt_respawns == 1  # rate-limited preempt respawn


def test_launcher_preempted_rc_propagates_without_elastic(tmp_path):
    script = os.path.join(str(tmp_path), "preempt.py")
    with open(script, "w") as f:
        f.write(f"import sys; sys.exit({PREEMPTED_RC})\n")
    launcher = _launcher(tmp_path, nproc_per_node=1, elastic=False)
    assert launcher.run([sys.executable, script]) == PREEMPTED_RC


# ==========================================================================
# preemption guard + TrainEpochRange emergency checkpoint
# ==========================================================================

def test_preemption_guard_simulate_and_env(monkeypatch):
    g = PreemptionGuard(install=False)
    assert not g.preempted
    assert rb.preemption.simulate() >= 1
    assert g.preempted
    g.clear()
    monkeypatch.setenv("PADDLE_TPU_PREEMPTION_SIGNAL", "SIGUSR1,SIGTERM")
    g2 = PreemptionGuard(install=False)
    assert list(g2.signals) == [signal.SIGUSR1, signal.SIGTERM]
    monkeypatch.setenv("PADDLE_TPU_PREEMPTION_SIGNAL", "NOTASIG")
    with pytest.raises(ValueError, match="NOTASIG"):
        PreemptionGuard(install=False)


def test_preemption_guard_real_signal_handler():
    g = PreemptionGuard(signals=[signal.SIGUSR1])  # install for real
    try:
        assert not g.preempted
        os.kill(os.getpid(), signal.SIGUSR1)
        deadline = time.time() + 5
        while not g.preempted and time.time() < deadline:
            time.sleep(0.01)
        assert g.preempted
    finally:
        g.uninstall()


def test_epoch_range_drains_emergency_checkpoint_on_simulated_preempt(
        tmp_path):
    """Chaos Preempt at the epoch-2 boundary: TrainEpochRange saves a
    synchronous emergency checkpoint and exits PREEMPTED_RC; a fresh range
    resumes at epoch 3."""
    state = {"w": 0.0}
    def mk_range():
        r = TrainEpochRange(6, checkpoint_dir=str(tmp_path),
                            save_interval=100,  # periodic saves OFF
                            preemption_guard=PreemptionGuard(install=False))
        r.register("s", lambda: dict(state), state.update)
        return r

    plan = rb.FaultPlan().inject("train.epoch", fp.Preempt(), at=2)
    done = []
    with rb.chaos(plan):
        with pytest.raises(SystemExit) as ei:
            for epoch in mk_range().get():
                state["w"] += 1.0
                done.append(epoch)
    assert ei.value.code == PREEMPTED_RC
    plan.assert_all_fired()
    assert done == [0, 1, 2]
    # the emergency checkpoint is on disk (epoch 2) and resume continues
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    assert mgr.latest_step() == 2
    state2 = {"w": -99.0}
    r2 = TrainEpochRange(6, checkpoint_dir=str(tmp_path), save_interval=100)
    r2.register("s", lambda: dict(state2), state2.update)
    resumed = [e for e in r2.get()]
    assert resumed == [3, 4, 5]
    assert state2["w"] == 3.0          # restored from the emergency save


def test_epoch_range_resume_falls_back_past_corrupt_newest(tmp_path):
    """Auto-resume must ride the newest→older fallback: bit-rot on the
    newest checkpoint resumes from the older one instead of failing."""
    state = {"w": 0.0}
    r = TrainEpochRange(4, checkpoint_dir=str(tmp_path), save_interval=1)
    r.register("s", lambda: dict(state), state.update)
    for _epoch in r.get():
        state["w"] += 1.0
    newest = max(r.manager.all_steps())
    shard = os.path.join(str(tmp_path), f"ckpt-{newest}", "host-0.ckpt")
    os.truncate(shard, os.path.getsize(shard) // 2)
    state2 = {"w": -1.0}
    r2 = TrainEpochRange(6, checkpoint_dir=str(tmp_path), save_interval=100)
    r2.register("s", lambda: dict(state2), state2.update)
    with pytest.warns(CheckpointFallbackWarning):
        resumed = list(r2.get())
    # fell back to ckpt-(newest-1): epoch counter and state both from it
    assert resumed == list(range(newest, 6))
    assert state2["w"] == float(newest)


_SIGTERM_SCRIPT = textwrap.dedent("""
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.incubate.checkpoint import TrainEpochRange
    from paddle_tpu.io import DataLoader, TensorDataset

    ckdir, mode = sys.argv[1], sys.argv[2]
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(4, 16), nn.ReLU(), nn.Linear(16, 1))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())
    step = TrainStep(net, nn.functional.mse_loss, opt)
    rng = np.random.RandomState(0)
    data = [(rng.randn(8, 4).astype('float32'),
             rng.randn(8, 1).astype('float32')) for _ in range(4)]

    r = TrainEpochRange(8, checkpoint_dir=ckdir, save_interval=100,
                        preemption_guard=True)
    r.register_train_step(step)
    losses = []
    ready = os.path.join(ckdir, "epoch_done")
    for epoch in r.get():
        for x, y in data:
            losses.append(float(step(paddle.to_tensor(x),
                                     paddle.to_tensor(y))))
        open(ready, "a").write("%d\\n" % epoch)
        if mode == "wait_for_sigterm" and epoch == 1:
            # signal readiness, then linger INSIDE the epoch body so the
            # SIGTERM arrives mid-epoch; the boundary check fires next
            open(os.path.join(ckdir, "ready_for_term"), "w").close()
            time.sleep(30)
    print("LOSSES", ",".join("%.10f" % l for l in losses))
""")


@pytest.mark.slow
def test_sigterm_emergency_checkpoint_and_bitwise_resume(tmp_path):
    """Real SIGTERM mid-epoch: the worker drains an emergency checkpoint,
    exits PREEMPTED_RC, and the resumed run reproduces the uninterrupted
    run's loss trajectory bit-identically (the
    test_kill_and_resume_identical_trajectory contract, but for
    preemption instead of a crash)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"

    # uninterrupted reference
    ref_dir = os.path.join(str(tmp_path), "ref")
    os.makedirs(ref_dir)
    ref = subprocess.run(
        [sys.executable, "-c", _SIGTERM_SCRIPT, ref_dir, "ok"],
        capture_output=True, text=True, timeout=600, cwd="/root/repo",
        env=env)
    assert ref.returncode == 0, ref.stderr[-2000:]
    ref_losses = ref.stdout.split("LOSSES ")[1].strip().split(",")

    # preempted run: SIGTERM once epoch 1 is mid-flight
    ck = os.path.join(str(tmp_path), "preempted")
    os.makedirs(ck)
    proc = subprocess.Popen(
        [sys.executable, "-c", _SIGTERM_SCRIPT, ck, "wait_for_sigterm"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd="/root/repo", env=env)
    ready = os.path.join(ck, "ready_for_term")
    deadline = time.time() + 300
    while not os.path.exists(ready) and time.time() < deadline:
        if proc.poll() is not None:
            raise AssertionError(
                "worker died early: " + proc.communicate()[1][-2000:])
        time.sleep(0.1)
    assert os.path.exists(ready), "worker never reached epoch 1"
    proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == PREEMPTED_RC, (proc.returncode, err[-2000:])
    mgr = CheckpointManager(ck, async_save=False)
    assert mgr.latest_step() == 1      # the emergency checkpoint

    resumed = subprocess.run(
        [sys.executable, "-c", _SIGTERM_SCRIPT, ck, "ok"],
        capture_output=True, text=True, timeout=600, cwd="/root/repo",
        env=env)
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    res_losses = resumed.stdout.split("LOSSES ")[1].strip().split(",")
    # epochs 2..7 of the resumed run == reference, bit-identical
    assert res_losses == ref_losses[2 * 4:]


# ==========================================================================
# divergence sentinel
# ==========================================================================

class _StubStep:
    """Minimal state_dict/set_state_dict holder for detector-logic tests."""

    def __init__(self):
        self.state = {"w": 0.0}

    def state_dict(self):
        return dict(self.state)

    def set_state_dict(self, sd):
        self.state = dict(sd)


def test_sentinel_spike_detection_and_ring_exhaustion():
    stub = _StubStep()
    s = DivergenceSentinel(stub, window=8, spike_factor=5.0, min_history=3,
                           snapshot_every=1, max_snapshots=2)
    for i in range(4):
        stub.state["w"] = float(i)
        assert s.observe(i, 1.0 + 0.01 * i) is None
    assert s.snapshots_available == 2
    # spike > 5x median: trips, rewinds to newest snapshot (step 3)
    with pytest.warns(rb.sentinel.DivergenceWarning):
        assert s.observe(4, 50.0) == 3
    assert stub.state["w"] == 3.0
    # immediate re-trip falls back to the older snapshot (step 2)
    with pytest.warns(rb.sentinel.DivergenceWarning):
        assert s.observe(4, float("inf")) == 2
    assert stub.state["w"] == 2.0
    # ring dry: loud typed error
    with pytest.raises(DivergenceError, match="exhausted"):
        s.observe(4, float("nan"))


def test_sentinel_scaler_skip_grace():
    """A NaN the fp16 GradScaler already SKIPPED must not trigger a rewind
    (params were never touched) — until the grace budget runs out."""
    from paddle_tpu.amp import GradScaler
    stub = _StubStep()
    scaler = GradScaler(enable=True)
    scaler._last_skipped = True        # as after a skipped fp16 step
    s = DivergenceSentinel(stub, scaler=scaler, snapshot_every=1,
                           max_snapshots=2, scaler_grace=3)
    s.observe(0, 1.0)
    s.observe(1, 1.0)
    assert s.observe(2, float("nan")) is None  # skip 1: grace
    assert s.observe(3, float("nan")) is None  # skip 2: grace
    with pytest.warns(rb.sentinel.DivergenceWarning):
        assert s.observe(4, float("nan")) == 1  # grace exhausted: rewind
    assert s.rewinds and s.rewinds[-1][0] == 4


def test_sentinel_nan_injection_rewind_restores_trajectory():
    """End-to-end: NaN grads injected at step 5 of a real TrainStep; the
    sentinel rewinds (params + opt + RNG) and the replayed steps produce
    the clean run's losses bit-identically."""
    data = _data(10)

    def run(with_fault):
        step = _tiny_step(seed=7)
        sentinel = DivergenceSentinel(step, snapshot_every=1,
                                      max_snapshots=3, min_history=3)
        losses = {}
        plan = rb.FaultPlan().inject("train.grads", fp.NaNBatch(), at=5) \
            if with_fault else None
        import contextlib
        scope = rb.chaos(plan) if plan is not None else \
            contextlib.nullcontext()
        with scope:
            i = 0
            while i < 10:
                loss = step(paddle.to_tensor(data[i][0]),
                            paddle.to_tensor(data[i][1]))
                resumed = sentinel.observe(i, float(loss))
                if resumed is not None:
                    i = resumed + 1    # replay from after the snapshot
                    continue
                losses[i] = float(loss)
                i += 1
        if plan is not None:
            plan.assert_all_fired()
        return [losses[i] for i in range(10)], sentinel

    clean, _ = run(False)
    chaotic, sentinel = run(True)
    assert len(sentinel.rewinds) == 1
    assert all(np.isfinite(v) for v in chaotic)
    np.testing.assert_array_equal(np.array(clean), np.array(chaotic))


def test_divergence_monitor_callback_rewinds_hapi_model():
    from paddle_tpu.callbacks import DivergenceMonitor

    cb = DivergenceMonitor(max_rewinds=2, snapshot_every=1, min_history=3)

    class FakeModel:
        _train_step = _StubStep()
        stop_training = False

    cb.set_model(FakeModel)
    for i in range(4):
        FakeModel._train_step.state["w"] = float(i)
        cb.on_train_batch_end(i, {"loss": 1.0})
    with pytest.warns(rb.sentinel.DivergenceWarning):
        cb.on_train_batch_end(4, {"loss": float("nan")})
    assert cb.rewinds == 1 and FakeModel._train_step.state["w"] == 3.0
    with pytest.warns(rb.sentinel.DivergenceWarning):
        cb.on_train_batch_end(5, {"loss": float("nan")})
    assert cb.rewinds == 2 and FakeModel.stop_training  # budget exhausted


# ==========================================================================
# amp faultpoint composition
# ==========================================================================

def test_forced_found_inf_skips_update_and_sets_flag():
    from paddle_tpu.amp import GradScaler
    net = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    scaler = GradScaler(enable=True, init_loss_scaling=8.0,
                        decr_every_n_nan_or_inf=1)
    x = paddle.to_tensor(np.random.randn(4, 4).astype("float32"))
    w_before = net.weight.numpy().copy()
    loss = scaler.scale(net(x).sum())
    loss.backward()
    plan = rb.FaultPlan().inject("amp.found_inf", fp.ForceFoundInf())
    with rb.chaos(plan):
        scaler.step(opt)
    plan.assert_all_fired()
    assert scaler.last_step_skipped
    np.testing.assert_array_equal(net.weight.numpy(), w_before)  # skipped
    assert scaler.get_loss_scaling() == 4.0  # dynamic scale backed off
    opt.clear_grad()


# ==========================================================================
# flight recorder (ISSUE 9): black-box dumps on faultpoint/recompile/
# divergence/preemption triggers, asserted through the PR-4 chaos hooks
# ==========================================================================

def _load_dump(path):
    with open(path) as f:
        return json.load(f)


def _assert_dump_shape(doc, trigger_kind):
    """Shared flight-dump assertions: the triggering event is IN the
    ring, the last-N ring is bounded, and the metrics snapshot is
    catalog-valid (every name declared — the acceptance contract)."""
    from paddle_tpu.observability import CATALOG
    assert doc["format"] == "paddle_tpu-flight-v1"
    assert doc["trigger"]["kind"] == trigger_kind
    ring = doc["ring"]
    assert 0 < len(ring) <= doc["ring_capacity"]
    assert ring[-1]["kind"] == "trigger"  # the trigger is the newest entry
    assert set(doc["metrics"]) <= set(CATALOG), \
        "flight metrics snapshot carries undeclared names: %r" \
        % (set(doc["metrics"]) - set(CATALOG))
    assert isinstance(doc["engines"], list)
    assert isinstance(doc["compile_counts"], dict)


def test_flight_dump_on_injected_publish_fault(tmp_path):
    """An injected checkpoint.publish fault that raises must leave a
    flight dump holding the triggering faultpoint event, the last-N
    ring, and a catalog-valid metrics snapshot."""
    from paddle_tpu.observability import flight
    rec = flight.enable(dir=str(tmp_path / "flight"))
    try:
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        plan = rb.FaultPlan().inject("checkpoint.publish", fp.DiskFull())
        with rb.chaos(plan):
            with pytest.raises(OSError):
                mgr.save(1, {"v": np.arange(4.0)})
        plan.assert_all_fired()
        path = flight.last_dump_path()
        assert path is not None and os.path.exists(path)
        doc = _load_dump(path)
        _assert_dump_shape(doc, "faultpoint")
        assert doc["trigger"]["site"] == "checkpoint.publish"
        fires = [e for e in doc["ring"] if e["kind"] == "faultpoint"
                 and e["site"] == "checkpoint.publish"]
        assert fires, "the firing event itself must be in the ring"
    finally:
        flight.disable()


def test_flight_dump_on_strict_recompile(tmp_path, monkeypatch):
    """A strict-mode RecompileError (the watchdog's fatal kill switch)
    dumps the flight ring before raising."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability import flight
    from paddle_tpu.observability.watchdog import RecompileError, watch
    monkeypatch.setenv("PADDLE_TPU_STRICT_COMPILE", "1")
    rec = flight.enable(dir=str(tmp_path))
    try:
        entry = watch("test.flight_entry", jax.jit(lambda x: x + 1),
                      expected=1)
        entry(jnp.zeros((2,), jnp.float32))           # budgeted compile
        with pytest.raises(RecompileError):
            entry(jnp.zeros((3,), jnp.float32))       # shape drift
        path = flight.last_dump_path()
        assert path is not None and os.path.exists(path)
        doc = _load_dump(path)
        _assert_dump_shape(doc, "recompile")
        assert doc["trigger"]["entry"] == "test.flight_entry"
        assert doc["trigger"]["compile_count"] == 2
        growth = [e for e in doc["ring"] if e["kind"] == "recompile"]
        assert len(growth) >= 2  # both compiles metered into the ring
    finally:
        flight.disable()


def test_flight_dump_on_divergence_ring_exhausted(tmp_path):
    from paddle_tpu.observability import flight
    from paddle_tpu.robustness.sentinel import DivergenceSentinel
    rec = flight.enable(dir=str(tmp_path))
    try:
        s = DivergenceSentinel(_StubStep(), min_history=1)
        with pytest.raises(DivergenceError):
            s.observe(0, float("nan"))   # no snapshot yet: ring dry
        doc = _load_dump(flight.last_dump_path())
        _assert_dump_shape(doc, "divergence")
    finally:
        flight.disable()


def test_flight_dump_on_preemption_guard_fire(tmp_path):
    from paddle_tpu.observability import flight
    rec = flight.enable(dir=str(tmp_path))
    try:
        g = PreemptionGuard(install=False)
        plan = rb.FaultPlan().inject("train.epoch", fp.Preempt())
        with rb.chaos(plan):
            fp.faultpoint("train.epoch")
        plan.assert_all_fired()
        assert g.preempted
        doc = _load_dump(flight.last_dump_path())
        _assert_dump_shape(doc, "preemption")
        # the guard fired FROM a faultpoint: both events share the ring
        kinds = [e["kind"] for e in doc["ring"]]
        assert "faultpoint" in kinds and "preemption" in kinds
    finally:
        flight.disable()
        g.clear()


def test_flight_disabled_is_noop(tmp_path):
    """Registry discipline: with no recorder armed, record() and the
    crash triggers cost a global None check and write nothing."""
    from paddle_tpu.observability import flight
    assert flight.active() is None
    assert flight.record("anything", x=1) is None
    assert flight.crash_dump({"kind": "nope"}) is None
    plan = rb.FaultPlan().inject("checkpoint.publish", fp.DiskFull())
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    with rb.chaos(plan):
        with pytest.raises(OSError):
            mgr.save(1, {"v": 1})
    assert flight.last_dump_path() is None
    assert not [p for p in os.listdir(str(tmp_path))
                if p.startswith("flight-")]


def test_flight_ring_is_bounded_and_engine_state_collected(tmp_path):
    from paddle_tpu.observability import flight
    rec = flight.enable(dir=str(tmp_path), capacity=8)
    try:
        for i in range(50):
            flight.record("tick", i=i)
        path = rec.dump({"kind": "manual"})
        doc = _load_dump(path)
        assert len(doc["ring"]) == 8          # drop-oldest, fixed size
        assert doc["ring"][-1]["kind"] == "trigger"
        assert doc["ring"][-2]["i"] == 49     # newest ticks survive
    finally:
        flight.disable()


def test_flight_dump_contains_hbm_ledger_snapshot(tmp_path):
    """ISSUE-11 acceptance: a crash dump embeds the HBM ledger — fresh
    per-device live bytes, the top-arrays breakdown ("what held the
    memory"), and the registered engine's KV-pool pricing — whether or
    not periodic sampling was armed; when armed, the last periodic
    sample rides along too."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import flight, hbm
    from paddle_tpu.serving.engine import DecodeEngine

    cfg = GPTConfig.tiny()
    cfg.hidden_dropout_prob = cfg.attention_dropout_prob = 0.0
    engine = DecodeEngine(GPTForCausalLM(cfg), num_slots=2, max_len=64,
                          page_size=8, seed=0)
    flight.enable(dir=str(tmp_path))
    try:
        # unarmed ledger: the dump still collects fresh state
        path = flight.crash_dump({"kind": "manual"})
        doc = _load_dump(path)
        assert doc["hbm"]["armed"] is False
        assert doc["hbm"]["devices"], "no per-device live bytes in dump"
        assert doc["hbm"]["live_bytes_total"] > 0
        assert doc["hbm"]["top_arrays"], "no what-held-the-memory table"
        top = doc["hbm"]["top_arrays"][0]
        assert top["nbytes"] > 0 and top["shape"] and top["dtype"]
        assert doc["hbm"]["kv_pool_bytes"] >= engine.kv_pool_bytes()
        # armed ledger: the last periodic sample is preserved in dumps
        hbm.enable()
        hbm.sample("pre-crash")
        doc2 = _load_dump(flight.crash_dump({"kind": "manual"}))
        assert doc2["hbm"]["armed"] is True
        assert doc2["hbm"]["last_sample"]["tag"] == "pre-crash"
    finally:
        hbm.disable()
        flight.disable()


def test_flight_dump_deferred_out_of_signal_frame(tmp_path):
    """A REAL signal's handler must not dump synchronously (it may have
    interrupted a frame holding the flight/metric locks) — the dump is
    deferred to the first `preempted` poll, the drain boundary."""
    from paddle_tpu.observability import flight
    flight.enable(dir=str(tmp_path))
    try:
        g = PreemptionGuard(install=False)
        g._on_signal(signal.SIGTERM, None)     # handler frame: no dump
        assert flight.last_dump_path() is None
        assert g._flag.is_set()
        assert g.preempted                     # safe frame: dump fires
        doc = _load_dump(flight.last_dump_path())
        _assert_dump_shape(doc, "preemption")
        assert doc["trigger"]["source"] == "signal:SIGTERM"
        n = len(doc["ring"])
        assert g.preempted                     # polled again: ONE dump
        assert len(flight.active().dumps) == 1
        g.clear()
        assert g._pending_flight is None
    finally:
        flight.disable()
        g.clear()


# ==========================================================================
# serving front-end chaos (ISSUE 13: serve.stream + guard-fire drain)
# ==========================================================================

def _serve_frontend(queue_limit=8, guard=None):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.engine import DecodeEngine
    from paddle_tpu.serving.frontend import ServingFrontend
    paddle.seed(0)
    cfg = GPTConfig.tiny()
    cfg.hidden_dropout_prob = cfg.attention_dropout_prob = 0.0
    model = GPTForCausalLM(cfg)
    model.eval()
    engine = DecodeEngine(model, num_slots=2, max_len=64, seed=0,
                          page_size=8)
    fe = ServingFrontend(engine, queue_limit=queue_limit, guard=guard)
    fe.start()
    return fe, engine


def _serve_post(fe, payload, read_all=True):
    import socket as _socket
    s = _socket.create_connection((fe.host, fe.port), timeout=60)
    body = json.dumps(payload).encode()
    s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: c\r\n"
              b"Content-Length: %d\r\n\r\n" % len(body) + body)
    if not read_all:
        return s
    buf = b""
    while True:
        b = s.recv(65536)
        if not b:
            break
        buf += b
    s.close()
    return buf


def test_serve_stream_site_declared():
    """Importing the front-end registers its chaos site (the registry
    mirrors the instrumentation, ROBUSTNESS.md discipline)."""
    import paddle_tpu.serving.frontend  # noqa: F401
    assert "serve.stream" in fp.SITES


def test_hang_action_sleeps_at_site_and_composes(monkeypatch):
    """ISSUE 14: the Hang action is an injected *stall*, not a crash —
    the site completes after the sleep, nothing raises, and it rides
    the normal plan schedules (the liveness suite proves the watchdog
    catches it at the beacon-covered sites; serve.step is the
    scheduler-loop injection point it added)."""
    import time as _time

    import paddle_tpu.serving.scheduler  # noqa: F401  (declares the site)
    assert "serve.step" in fp.SITES
    fp.declare("test.chaos_hang", "suite probe")
    plan = fp.FaultPlan(seed=0).inject("test.chaos_hang", fp.Hang(0.05),
                                       every=2, times=1)
    with fp.chaos(plan):
        t0 = _time.perf_counter()
        ctx = fp.faultpoint("test.chaos_hang", payload=1)
        assert _time.perf_counter() - t0 >= 0.05
        assert ctx["payload"] == 1          # ctx untouched: pure stall
        t0 = _time.perf_counter()
        fp.faultpoint("test.chaos_hang")    # times=1 exhausted
        assert _time.perf_counter() - t0 < 0.05
    plan.assert_all_fired()


@pytest.mark.slow
def test_injected_stream_reset_cancels_and_frees_pages():
    """A SocketReset injected at the serve.stream site (= the client
    vanished mid-stream) must cancel the request, free its slot AND its
    pages refcount-exactly (no pool leak), and leave the engine
    serviceable — the NEXT request completes normally."""
    fe, engine = _serve_frontend()
    try:
        plan = fp.FaultPlan(seed=0).inject(
            "serve.stream", fp.SocketReset(), at=2)
        with fp.chaos(plan):
            raw = _serve_post(fe, {"prompt": [5, 6, 7, 8],
                                   "max_new_tokens": 40,
                                   "temperature": 0.0})
        plan.assert_all_fired()
        # the stream was cut mid-flight: no done event reached us
        assert b'"done": true' not in raw
        deadline = time.time() + 30
        while time.time() < deadline and engine._alloc.pages_used():
            time.sleep(0.02)
        assert engine._alloc.pages_used() == 0, "page leak after reset"
        res = list(fe.scheduler.finished.values())
        assert res and res[0].finish_reason == "cancelled"
        # the engine survived: a fresh request runs to completion
        raw2 = _serve_post(fe, {"prompt": [5, 6, 7, 8],
                                "max_new_tokens": 3,
                                "temperature": 0.0})
        assert b'"done": true' in raw2
        assert engine.decode_compile_count == 1
    finally:
        fe.stop()
    assert engine._alloc.pages_used() == 0


@pytest.mark.slow
def test_preempt_during_serve_requeues_not_drops():
    """The chaos Preempt action (simulated SIGTERM) fires while requests
    are in flight: the front-end drains — every accepted request
    finishes with its FULL token stream (requeue-not-drop is the
    scheduler's job under pressure; the drain's job is to never cut a
    stream) — and new requests shed 503."""
    guard = PreemptionGuard(install=False)
    fe, engine = _serve_frontend(guard=guard)
    try:
        s = _serve_post(fe, {"prompt": [9, 8, 7], "max_new_tokens": 10,
                             "temperature": 0.0}, read_all=False)
        plan = fp.FaultPlan(seed=0).inject("train.epoch", fp.Preempt(),
                                           at=0)
        with fp.chaos(plan):
            fp.faultpoint("train.epoch")   # any site: Preempt flips guards
        plan.assert_all_fired()
        buf = b""
        while True:
            b = s.recv(65536)
            if not b:
                break
            buf += b
        s.close()
        assert b'"finish_reason": "length"' in buf
        assert buf.count(b"data: {\"tokens\"") == 10   # full stream
        assert fe.wait_drained(30)
        raw = _serve_post(fe, {"prompt": [1], "max_new_tokens": 1})
        assert b"503" in raw.split(b"\r\n")[0]
    finally:
        guard.clear()
        fe.stop()
