"""The training window's heartbeat: a thread that only wakes and notes the
clock, so that a window that lost seconds to one wait says whether the host
ran meanwhile (PERF.md section 7: stalls of seconds with nothing compiling)."""
import threading
import time
import types

import jax.numpy as jnp

from benchmarks.lib import train


def test_the_longest_gaps_name_a_host_that_was_not_scheduled():
    wakes = [10.0, 10.02, 10.04, 13.04, 13.06, 13.09]
    assert train._longest_gaps(wakes, 10.0, count=1) == [[0.04, 3.0]]
    longest = train._longest_gaps(wakes, 10.0)
    assert len(longest) == 3 and longest[1] == [3.06, 0.03]
    assert train._longest_gaps([10.0], 10.0) == []


def test_the_window_runs_the_heartbeat_and_stops_it():
    def step(x, labels):
        time.sleep(0.03)                 # a step the host waits for
        return types.SimpleNamespace(_array=x)
    batches = (jnp.zeros((2, 4), jnp.int32),) * 2
    losses, window_s, paused, trace, stamps, host_gaps = (
        train._timed_window(step, batches, 0.4, 0))
    assert len(losses) == len(stamps) >= 5 and trace is None and paused == 0
    assert 0.4 <= window_s < 2.0
    # the host ran all through: every gap is a heartbeat and a little
    assert len(host_gaps) == 3
    for at, gap in host_gaps:
        assert -0.1 <= at <= window_s and 0 < gap < 0.3
    assert host_gaps[0][1] >= 0.5 * train.HEARTBEAT_S
    assert not any(t.name == "bench-heartbeat"
                   for t in threading.enumerate())
