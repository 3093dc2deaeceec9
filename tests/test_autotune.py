"""Kernel autotuner (paddle_tpu/kernels/autotune.py): cache round-trip,
override precedence, deterministic selection under fake timers, and the
bit-identical-program guarantee when tuning is disabled."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import autotune as at
from paddle_tpu.kernels import flash_attention_pallas as fap


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test gets its own cache file, a clean memo and no pins."""
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    monkeypatch.delenv("PADDLE_TPU_AUTOTUNE", raising=False)
    monkeypatch.delenv("PADDLE_TPU_AUTOTUNE_PIN", raising=False)
    from paddle_tpu.utils import flags
    monkeypatch.setitem(flags._REGISTRY, "autotune", False)
    monkeypatch.setitem(flags._REGISTRY, "autotune_pin", "")
    at._MEMO.clear()
    at._MEMO_DEFAULT.clear()
    at._RESOLVED.clear()
    at._CACHE = None
    at._CACHE_LOADED_FROM = None
    yield
    at._MEMO.clear()
    at._MEMO_DEFAULT.clear()
    at._RESOLVED.clear()
    at._CACHE = None
    at._CACHE_LOADED_FROM = None


# -- the tuner's vehicle: a family of the test's own --------------------------
# Row sums of an (n, f) array taken ``block_rows`` rows at a time: a plain
# jnp function with one config knob, registered for this module only.

def _rows_candidates(key):
    n = key["n"]
    br0 = min(64, n)
    cands = [{"variant": "base", "config": {"block_rows": br0}}]
    for br in (1024, 512, 256, 128, 64, 32, 16, 8):
        if br != br0 and br <= n and n % br == 0:
            cands.append({"variant": "base", "config": {"block_rows": br}})
    return cands


def _rows_runner(cand, key):
    n, f, br = key["n"], key["f"], cand["config"]["block_rows"]
    x = jnp.ones((n, f), jnp.dtype(key["dtype"]))
    fn = jax.jit(lambda a: a.reshape(n // br, br, f).sum(axis=1))
    return lambda: jax.block_until_ready(fn(x))


@pytest.fixture(autouse=True, scope="module")
def _rows_family():
    at.register_family("rows", _rows_candidates, _rows_runner)
    yield
    at._FAMILIES.pop("rows", None)


@pytest.fixture(autouse=True, scope="module")
def _settled_worker():
    """This file's first test starts on a worker that has nothing of an
    earlier file's still in flight.  The one red run of that test (the
    driver's take-up run of PR 31's tree) was no assertion: the worker died
    of a segmentation fault on a thread with no Python frame (faulthandler
    listed the main thread inside ``resolve`` reading the cache file, and
    execnet's reader, neither as the current one) — work another file of
    that worker had dispatched, not a state of the autotuner, which
    ``_isolated_cache`` already sets up for every test.  So: collect what
    the earlier files dropped and wait for what they dispatched."""
    import gc
    gc.collect()
    jax.effects_barrier()
    jax.block_until_ready(jax.live_arrays())
    yield


ROWS_KEY = dict(n=64, f=256, dtype="float32", platform="cpu")


def _fake_timer(table):
    """Deterministic per-candidate-signature timer."""
    def fake(fn, samples):
        return table[fake.current_sig]
    return fake


def test_disabled_resolve_returns_registered_default():
    cand = at.resolve("rows", ROWS_KEY)
    assert cand == _rows_candidates(ROWS_KEY)[0]
    # flash too: the default candidate IS the hand-tuned config
    fkey = fap.autotune_key(1, 256, 256, 2, 64, jnp.float32, True)
    cand = at.resolve("flash_fwd", fkey)
    assert cand["variant"] == "base"
    assert cand["config"] == {"block_q": 256, "block_k": 256, "hg": 2}


def test_tune_selects_fastest_and_caches(monkeypatch):
    cands = _rows_candidates(ROWS_KEY)
    want = cands[2]          # an arbitrary non-default candidate

    def fake_time(fn, samples):
        return 0.5   # overwritten below per candidate via runner identity
    # key the fake timing on the candidate order: tune() walks candidates
    # in order, so feed times from a list
    times = [5.0] * len(cands)
    times[2] = 1.0
    it = iter(times)
    monkeypatch.setattr(at, "_time_callable", lambda fn, s: next(it))
    chosen = at.tune("rows", ROWS_KEY)
    assert chosen["config"] == want["config"]
    # persisted: a fresh process (memo cleared, cache reloaded) resolves
    # to the tuned pick without re-timing
    at._MEMO.clear()
    at._CACHE = None
    monkeypatch.setattr(at, "_time_callable",
                        lambda fn, s: pytest.fail("re-timed a cached key"))
    assert at.resolve("rows", ROWS_KEY)["config"] == want["config"]
    # the cache file records the full timing table
    with open(at.cache_path()) as f:
        data = json.load(f)
    entry = data["families"]["rows"][at.key_str(ROWS_KEY)]
    assert entry["config"] == want["config"]
    assert len(entry["timings"]) == len(cands)


def test_tune_is_deterministic_under_equal_timers(monkeypatch):
    """Equal fake times -> the FIRST candidate (hand-tuned default) wins:
    selection is strict-improvement only."""
    monkeypatch.setattr(at, "_time_callable", lambda fn, s: 1.0)
    chosen = at.tune("rows", ROWS_KEY)
    assert chosen == _rows_candidates(ROWS_KEY)[0]


def test_failed_candidates_are_skipped(monkeypatch):
    cands = _rows_candidates(ROWS_KEY)
    calls = {"n": 0}

    def runner(cand, key):
        if cand == cands[0]:
            raise RuntimeError("VMEM OOM (simulated)")
        return lambda: None

    fam = at.families()["rows"]
    monkeypatch.setattr(fam, "runner", runner)
    monkeypatch.setattr(at._FAMILIES["rows"], "runner", runner)
    times = iter([3.0, 1.0] + [9.0] * len(cands))
    monkeypatch.setattr(at, "_time_callable", lambda fn, s: next(times))
    chosen = at.tune("rows", ROWS_KEY)
    assert chosen["config"] == cands[2]["config"]
    with open(at.cache_path()) as f:
        entry = json.load(f)["families"]["rows"][at.key_str(ROWS_KEY)]
    assert "failed" in str(entry["timings"][at._cand_sig(cands[0])])


def test_pin_overrides_cache_and_tuning(monkeypatch):
    # seed the cache with a tuned pick
    monkeypatch.setattr(at, "_time_callable", lambda fn, s: 1.0)
    at.tune("rows", ROWS_KEY)
    # env pin wins over the cache
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_PIN", "rows=base:block_rows=8")
    assert at.resolve("rows", ROWS_KEY)["config"]["block_rows"] == 8
    # FLAGS pin wins over the env pin
    from paddle_tpu.utils import flags
    monkeypatch.setitem(flags._REGISTRY, "autotune_pin",
                        "rows=base:block_rows=32")
    assert at.resolve("rows", ROWS_KEY)["config"]["block_rows"] == 32
    # partial pins merge over the default config
    monkeypatch.setitem(flags._REGISTRY, "autotune_pin", "rows=base")
    assert at.resolve("rows", ROWS_KEY) == _rows_candidates(ROWS_KEY)[0]


def test_pin_parsing_types_and_multiple_families():
    os.environ["PADDLE_TPU_AUTOTUNE_PIN"] = (
        "flash_fwd=bf16chain+iotafree:block_q=256,block_k=128;"
        "rows=base:block_rows=16")
    try:
        pins = at._pins()
        assert pins["flash_fwd"]["variant"] == "bf16chain+iotafree"
        assert pins["flash_fwd"]["config"] == {"block_q": 256,
                                               "block_k": 128}
        assert pins["rows"]["config"] == {"block_rows": 16}
    finally:
        del os.environ["PADDLE_TPU_AUTOTUNE_PIN"]


def test_corrupt_cache_falls_back_to_default():
    path = at.cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write("{not json")
    assert at.resolve("rows", ROWS_KEY) == _rows_candidates(ROWS_KEY)[0]


def test_invalid_cached_config_sanitized_at_kernel_level(monkeypatch):
    """A stale/corrupt cache entry with impossible blocks must not break
    the kernels — the flash wrapper falls back to the hand-tuned spec."""
    fkey = fap.autotune_key(1, 256, 256, 2, 64, jnp.float32, True)
    at._MEMO[("flash_fwd", at.key_str(fkey))] = {
        "variant": "base",
        "config": {"block_q": 999, "block_k": 7, "hg": 3}}
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)
    out = fap.flash_attention_bshd_native(q, q, q, causal=True,
                                          interpret=True)
    ref = fap._reference_bhsd(*[jnp.swapaxes(x, 1, 2) for x in (q, q, q)],
                              True, 1.0 / 8.0)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.swapaxes(ref, 1, 2)),
                               atol=1e-5, rtol=1e-5)


def test_warm_and_cli_smoke(capsys, monkeypatch):
    """warm() on a real (tiny) key + the CLI table/dump/clear paths."""
    key = dict(ROWS_KEY, n=16, f=128)
    results = at.warm([("rows", key)], verbose=False)
    assert results and "config" in results[0]
    at._cli_main(["table"])
    out = capsys.readouterr().out
    assert "rows [" in out and "chosen:" in out
    at._cli_main(["dump"])
    assert "families" in capsys.readouterr().out
    at._cli_main(["clear"])
    assert not os.path.isfile(at.cache_path())


def _hlo(fn, *args):
    # the module/entry name carries the python function name — scrub it so
    # only the PROGRAM is compared
    return re.sub(r"jit_\w+", "jit_f",
                  jax.jit(fn).lower(*args).as_text())


def test_bit_identical_programs_when_disabled():
    """With tuning disabled (no cache/pin), the autotune-resolved path
    must produce the SAME program as the explicit hand-tuned default,
    forward and backward (acceptance criterion)."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)

    def flash_auto(x):
        return fap.flash_attention_bshd_native(x, x, x, causal=True,
                                               interpret=True)

    def flash_hand(x):
        return fap.flash_attention_bshd_native(x, x, x, causal=True,
                                               interpret=True,
                                               variant="base")

    assert _hlo(flash_auto, q) == _hlo(flash_hand, q)

    # the backward's families resolve at trace time too
    def grad_of(f):
        return jax.grad(lambda x: jnp.sum(f(x)))

    assert _hlo(grad_of(flash_auto), q) == _hlo(grad_of(flash_hand), q)


def test_resolve_trace_safe():
    """resolve() runs at trace time inside jit — it must not execute any
    on-device work when tuning is disabled (pure host dict lookups)."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.float32)

    @jax.jit
    def f(x):
        return fap.flash_attention_bshd_native(x, x, x, causal=True,
                                               interpret=True)

    out = f(q)
    assert out.shape == q.shape
    assert ("flash_fwd", at.key_str(
        fap.autotune_key(1, 256, 256, 2, 64, jnp.float32, True))) \
        in at._MEMO_DEFAULT


def test_enabling_autotune_mid_process_still_tunes(monkeypatch):
    """A key first resolved with tuning OFF (default memo) must still be
    tuned when the flag is flipped later in the same process."""
    default = at.resolve("rows", ROWS_KEY)
    assert default == _rows_candidates(ROWS_KEY)[0]
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
    times = iter([9.0, 9.0, 1.0, 9.0, 9.0])
    monkeypatch.setattr(at, "_time_callable", lambda fn, s: next(times))
    tuned = at.resolve("rows", ROWS_KEY)
    assert tuned == _rows_candidates(ROWS_KEY)[2]


def test_multihost_gates_lazy_tuning(monkeypatch):
    """On multi-process jobs resolve() must NOT time candidates lazily
    (hosts could pick different variants and trace divergent programs);
    only deterministic cache/pin/default resolution is allowed — the CLI
    warm + shipped cache is the sanctioned path."""
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
    monkeypatch.setattr(at, "_single_process", lambda: False)
    monkeypatch.setattr(at, "_time_callable",
                        lambda fn, s: pytest.fail("timed on multihost"))
    assert at.resolve("rows", ROWS_KEY) == _rows_candidates(ROWS_KEY)[0]
    # explicit tune() (CLI warm) still works — pytest.fail above would
    # fire if it went through _time_callable, so un-patch first
    monkeypatch.setattr(at, "_time_callable", lambda fn, s: 1.0)
    at._MEMO.clear()
    assert at.tune("rows", ROWS_KEY) == _rows_candidates(ROWS_KEY)[0]


def test_report_snapshot():
    at.resolve("rows", ROWS_KEY)
    rep = at.report()
    assert rep["rows"][at.key_str(ROWS_KEY)]["config"]["block_rows"] == 64


def test_report_includes_pinned_families(monkeypatch):
    """The PERF.md attribution protocol pins one family and reads
    bench.py's 'autotune' field — pinned resolutions must appear in
    report(), not just memoised ones."""
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_PIN", "rows=base:block_rows=8")
    at.resolve("rows", ROWS_KEY)
    rep = at.report()
    assert rep["rows"][at.key_str(ROWS_KEY)]["config"]["block_rows"] == 8
