"""Compile phases of the watched entries as counters in the registry
(``observability/watchdog.py``): JAX's own trace, lowering and backend
durations filed under the entry that pays them, everything else under
``(unwatched)``; the package's import time as a gauge."""
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import registry as registry_mod
from paddle_tpu.observability.watchdog import UNWATCHED, watch

PHASES = ("trace", "lower", "backend")


def _seconds(entry):
    series = obs.default_registry().snapshot().get(
        "compile.phase_seconds", {"series": []})["series"]
    return {s["labels"]["phase"]: s["value"] for s in series
            if s["labels"]["entry"] == entry}


def _cache_events(entry):
    series = obs.default_registry().snapshot().get(
        "compile.cache", {"series": []})["series"]
    return sum(s["value"] for s in series if s["labels"]["entry"] == entry)


@pytest.fixture
def fresh_program():
    """A jitted function no other test compiles (its constant is its own)."""
    def phases_probe(x):
        return jnp.tanh(x) * 0.5772156649 + x
    return jax.jit(phases_probe)


def test_first_call_files_each_phase_and_the_second_none(fresh_program):
    entry = watch("test.phases", fresh_program, expected=1)
    x = jnp.ones((4, 4), jnp.float32)
    assert _seconds("test.phases") == {}
    cache_before = _cache_events("test.phases")
    entry(x).block_until_ready()
    first = _seconds("test.phases")
    assert sorted(first) == sorted(PHASES)
    assert all(first[p] > 0 for p in PHASES), first
    # the tests' persistent cache is on: one verdict, hit or miss
    assert _cache_events("test.phases") - cache_before == 1
    entry(x).block_until_ready()
    assert _seconds("test.phases") == first
    assert _cache_events("test.phases") - cache_before == 1


def test_a_jit_traced_inside_the_entry_is_not_counted_twice():
    inner = jax.jit(lambda a: a * 3.0)

    def outer_probe(x):
        return inner(x) + inner(x + 1.0)

    seen = []

    def listener(event, secs, fun_name=None, **kw):
        seen.append((event.rsplit("/", 1)[-1], fun_name, secs))

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        entry = watch("test.nested", jax.jit(outer_probe), expected=1)
        entry(jnp.ones((4,), jnp.float32)).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    own = sum(s for e, f, s in seen
              if e == "jaxpr_trace_duration" and f == "outer_probe")
    nested = [s for e, f, s in seen
              if e == "jaxpr_trace_duration" and f == "<lambda>"]
    assert nested, "the inner jit was traced inside the outer"
    assert _seconds("test.nested")["trace"] == pytest.approx(own)


def test_an_eager_op_lands_under_unwatched():
    before = _seconds(UNWATCHED)
    cache_before = _cache_events(UNWATCHED)
    # an eager op on a shape nothing else in the suite uses
    paddle.to_tensor(jnp.ones((3, 7, 5), jnp.float32)).cumsum(1).numpy()
    after = _seconds(UNWATCHED)
    assert after["backend"] > before.get("backend", 0.0)
    assert after["lower"] > before.get("lower", 0.0)
    assert _cache_events(UNWATCHED) > cache_before


def test_a_disabled_registry_records_nothing(monkeypatch, fresh_program):
    off = registry_mod.Registry(catalog=obs.CATALOG, enabled=False)
    monkeypatch.setattr(registry_mod, "_DEFAULT", off)
    assert registry_mod.counter(
        "compile.phase_seconds", ("entry", "phase")) is obs.NOOP_COUNTER
    entry = watch("test.phases_off", fresh_program, expected=1)
    entry(jnp.ones((2, 2), jnp.float32)).block_until_ready()
    assert off.snapshot() == {}
    monkeypatch.undo()
    assert _seconds("test.phases_off") == {}


def test_both_series_and_the_gauge_are_declared():
    assert obs.CATALOG["compile.phase_seconds"]["labels"] == ("entry",
                                                              "phase")
    assert obs.CATALOG["compile.cache"]["labels"] == ("entry", "result")
    assert obs.CATALOG["process.import_seconds"]["type"] == "gauge"


def test_import_seconds_is_recorded_without_starting_a_backend():
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, paddle_tpu\n"
         "from jax._src import xla_bridge\n"
         "from paddle_tpu import observability as obs\n"
         "assert not xla_bridge.backends_are_initialized()\n"
         "s = obs.default_registry().snapshot()['process.import_seconds']\n"
         "print(s['series'][0]['value'])"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert 0.0 < float(out.stdout.strip()) < 120.0
