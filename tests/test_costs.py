"""Compiled-program cost & HBM observability (ISSUE 11): ProgramReport
extraction (the one helper hapi.flops also routes through), MFU/BW-util derivation, the bench `cost` block + its schema and
trajectory gates, the TPU506 peak-HBM budget pass, the `programs` CLI,
the live HBM ledger (noop-identity when disarmed, sampled gauges +
chrome counter lanes when armed), and the engine/TrainStep report hooks."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import costs, hbm


# ---------------------------------------------------------------------------
# extraction shims
# ---------------------------------------------------------------------------

class _FakeCompiled:
    def __init__(self, ca=None, ma=None, raise_ca=False):
        self._ca, self._ma, self._raise = ca, ma, raise_ca

    def cost_analysis(self):
        if self._raise:
            raise NotImplementedError("backend reports nothing")
        return self._ca

    def memory_analysis(self):
        if self._raise:
            raise NotImplementedError
        return self._ma


class _FakeMem:
    argument_size_in_bytes = 100
    output_size_in_bytes = 10
    temp_size_in_bytes = 50
    alias_size_in_bytes = 40
    generated_code_size_in_bytes = 7


def test_cost_analysis_dict_handles_all_shapes():
    assert costs.cost_analysis_dict(
        _FakeCompiled(ca={"flops": 3.0})) == {"flops": 3.0}
    # degraded backends: None / raising -> {}
    assert costs.cost_analysis_dict(_FakeCompiled(ca=None)) == {}
    assert costs.cost_analysis_dict(_FakeCompiled(raise_ca=True)) == {}
    # strict mode (the hapi.flops path): a RAISING backend propagates —
    # flops() returns a bare int and must not answer 0 on failure
    with pytest.raises(NotImplementedError):
        costs.cost_analysis_dict(_FakeCompiled(raise_ca=True), strict=True)


def test_memory_analysis_dict_and_derived_peak():
    mem = costs.memory_analysis_dict(_FakeCompiled(ma=_FakeMem()))
    assert mem["argument_bytes"] == 100 and mem["alias_bytes"] == 40
    r = costs.report_from_compiled(
        "t", _FakeCompiled(ca={"flops": 1.0}, ma=_FakeMem()), backend="x")
    # peak = args + out + temp - alias (generated code EXCLUDED: the one
    # wildly backend-dependent term, not a data-buffer regression vector)
    assert r.peak_bytes == 100 + 10 + 50 - 40
    assert r.generated_code_bytes == 7
    # a backend with no memory analysis degrades to None, never a guess
    r2 = costs.report_from_compiled(
        "t", _FakeCompiled(ca={"flops": 1.0}, ma=None), backend="x")
    assert r2.peak_bytes is None and r2.argument_bytes is None
    assert r2.flops == 1.0 and r2.available


def test_report_from_real_compiled_program():
    c = jax.jit(lambda x: jnp.tanh(x @ x).sum()) \
        .lower(jnp.ones((64, 64))).compile()
    r = costs.report_from_compiled("tiny", c)
    assert r.available and r.flops and r.flops > 2 * 64 ** 3 * 0.9
    assert r.bytes_accessed and r.bytes_accessed >= 64 * 64 * 4
    assert r.peak_bytes and r.peak_bytes > 0
    d = r.as_dict()
    assert d["name"] == "tiny" and d["flops"] == r.flops
    json.dumps(d)    # JSON-ready (the CLI contract)


# ---------------------------------------------------------------------------
# MFU / bandwidth utilization
# ---------------------------------------------------------------------------

def test_mfu_and_bw_util_math():
    v5e = "TPU v5 lite"      # what one v5e chip reports as device_kind
    assert costs.mfu(197e12 * 0.005, 0.01, v5e) == pytest.approx(0.5)
    assert costs.bw_util(819e9 * 0.005, 0.01, v5e) == pytest.approx(0.5)
    # any unknown input -> None, never a fabricated 0.0
    assert costs.mfu(None, 0.01, v5e) is None
    assert costs.mfu(5e9, None, v5e) is None
    assert costs.mfu(5e9, 0.0, v5e) is None
    # a CPU has no peak (this process's own device, and by name)
    assert costs.mfu(5e9, 0.01) is None
    assert costs.mfu(5e9, 0.01, device_kind="cpu") is None
    assert costs.peak_flops("TPU v4") == 275e12
    assert costs.peak_hbm_bandwidth("TPU v5e") == 819e9
    # an accelerator the table does not know is an error, not a null
    with pytest.raises(ValueError, match="no published peak"):
        costs.peak_flops("TPU v9 hyper")


def test_cost_block_shape_and_chip_gating():
    r = costs.ProgramReport(name="t", flops=1e9, bytes_accessed=1e8,
                            peak_bytes=123)
    blk = costs.cost_block(r, step_seconds=0.01, on_chip=False)
    assert set(blk) == {"flops", "hbm_bytes", "peak_bytes", "mfu",
                       "bw_util"}
    assert blk["mfu"] is None and blk["bw_util"] is None   # off-chip
    blk = costs.cost_block(r, step_seconds=0.01, on_chip=True,
                           device_kind="TPU v5 lite")
    assert blk["mfu"] == pytest.approx(1e9 / (0.01 * 197e12), rel=1e-3)
    assert blk["bw_util"] == pytest.approx(1e8 / (0.01 * 819e9), rel=1e-3)


def test_hapi_flops_routes_through_the_shared_shim():
    """Satellite: hapi.flops no longer hand-rolls cost_analysis parsing —
    one parser (costs.cost_analysis_dict)."""
    import inspect

    from paddle_tpu import hapi, nn
    src = inspect.getsource(hapi.flops)
    assert "cost_analysis_dict" in src
    assert "isinstance(ca, (list, tuple))" not in src   # the old copy
    net = nn.Linear(8, 8)
    got = hapi.flops(net, input_size=[1, 8])
    assert got >= 2 * 8 * 8    # the matmul's MACs at least


# ---------------------------------------------------------------------------
# TPU506 — peak-HBM budgets
# ---------------------------------------------------------------------------

def _tpu506_program(name, budget, with_lowered=True):
    from paddle_tpu.analysis.trace import TraceProgram

    def fn(x):
        return (x @ x).sum()

    x = jnp.zeros((64, 64), jnp.float32)
    return TraceProgram(
        name=name, jaxpr=jax.make_jaxpr(fn)(x),
        lowered=(jax.jit(fn).lower(x) if with_lowered else None),
        meta={"kind": "fixture", "hbm_budget": budget})


def test_tpu506_budget_pass_semantics():
    from paddle_tpu.analysis.trace import HbmBudgetPass
    pz = HbmBudgetPass()
    # over budget: one finding at the stable pseudo-path
    over = list(pz.check(_tpu506_program("f/over", budget=16)))
    assert len(over) == 1 and over[0].rule == "TPU506"
    assert over[0].symbol == "memory/peak_bytes"
    assert "exceeds the declared budget" in over[0].message
    # roomy budget: silent
    assert list(pz.check(_tpu506_program("f/ok", budget=1 << 24))) == []
    # no budget declared: not this pass's business
    p = _tpu506_program("f/none", budget=16)
    del p.meta["hbm_budget"]
    assert list(pz.check(p)) == []
    # budgeted but unpriceable: LOUD (silent green is the failure mode)
    bad = list(pz.check(_tpu506_program("f/lost", budget=16,
                                        with_lowered=False)))
    assert len(bad) == 1 and "cannot be priced" in bad[0].message


def test_tpu506_peak_none_is_loud_for_budgeted_programs(monkeypatch):
    """A budgeted program whose memory_analysis reports NO buffer sizes
    (peak_bytes None — e.g. a jax upgrade renaming the fields) must be
    a finding, not a skip: the declared budget is unenforceable and the
    strict audit must not look green."""
    from paddle_tpu.analysis.trace import HbmBudgetPass
    monkeypatch.setattr(costs, "memory_analysis_dict", lambda c: {})
    out = list(HbmBudgetPass().check(_tpu506_program("f/nomem",
                                                     budget=1 << 24)))
    assert len(out) == 1 and "no buffer sizes" in out[0].message


def test_tpu506_budgets_declared_for_serving_entries():
    """Acceptance: at least the serving decode/prefill/verify budgets are
    declared (the strict CI audit then exercises them on every run)."""
    from paddle_tpu.analysis.trace import HBM_BUDGETS
    for name in ("serving/decode_step", "serving/prefill_chunk",
                 "serving/spec_verify"):
        assert name in HBM_BUDGETS and HBM_BUDGETS[name] > 0, name


def test_compile_program_caches_on_meta():
    p = _tpu506_program("f/cache", budget=None)
    c1 = costs.compile_program(p)
    assert c1 is not None and p.meta["_compiled"] is c1
    assert costs.compile_program(p) is c1    # second call: cache hit
    r = costs.report_for_program(p)
    assert r.available and r.peak_bytes > 0


# ---------------------------------------------------------------------------
# the `programs` CLI
# ---------------------------------------------------------------------------

@pytest.mark.slow   # tier-1 wall budget: runs unfiltered in CI (see ci.yml)
def test_programs_cli_pattern_subset(capsys):
    from paddle_tpu.observability.__main__ import main
    rc = main(["programs", "pallas/flash_fwd/base"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "pallas/flash_fwd/base" in out and "priced" in out
    rc = main(["programs", "pallas/flash_fwd/base", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc[0]["name"] == "pallas/flash_fwd/base"
    assert doc[0]["available"] and doc[0]["peak_bytes"] > 0
    # off-chip Pallas rows are labeled as interpret-mode pricing
    assert "interpret" in doc[0]["note"]


def test_programs_cli_empty_is_exit_2(capsys):
    from paddle_tpu.observability.__main__ import main
    rc = main(["programs", "no-such-program-*"])
    assert rc == 2
    assert "EMPTY registry" in capsys.readouterr().err


@pytest.mark.slow
def test_programs_cli_full_registry(capsys):
    """Acceptance: a FLOPs/bytes/peak-HBM row for all 40+ canonical
    programs (runs in the unfiltered CI observability job — the full
    registry build + compile is minutes, not tier-1 material)."""
    from paddle_tpu.observability.__main__ import main
    rc = main(["programs", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert len(doc) >= 40, "registry shrank: %d programs" % len(doc)
    unpriced = [r["name"] for r in doc if not r["available"]]
    assert not unpriced, "programs without a cost row: %s" % unpriced
    by_name = {r["name"]: r for r in doc}
    for name in ("gpt_train_step", "serving/decode_step",
                 "pallas/flash_fwd/base"):
        r = by_name[name]
        assert r["flops"] and r["bytes_accessed"] and r["peak_bytes"]


# ---------------------------------------------------------------------------
# engine / TrainStep report hooks
# ---------------------------------------------------------------------------

def _tiny_engine(**kw):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.engine import DecodeEngine
    paddle.seed(0)
    cfg = GPTConfig.tiny()
    cfg.hidden_dropout_prob = cfg.attention_dropout_prob = 0.0
    return DecodeEngine(GPTForCausalLM(cfg), num_slots=2, max_len=64,
                        seed=0, **kw)


def test_engine_kv_pool_bytes_accounting():
    e = _tiny_engine(page_size=16)
    assert e.kv_pool_bytes() == \
        e.num_pages * e.page_size * e.kv_row_bytes()
    s = _tiny_engine(paged=False)
    assert s.kv_pool_bytes() == s.num_slots * s.max_len * s.kv_row_bytes()
    # int8-aware via kv_row_bytes: codes + scales, not the bf16 rows
    q = _tiny_engine(page_size=16, kv_dtype="int8")
    assert q.kv_pool_bytes() < e.kv_pool_bytes()
    assert q.kv_pool_bytes() == \
        q.num_pages * q.page_size * q.kv_row_bytes()


@pytest.mark.slow
def test_engine_cost_reports_cover_watched_entries():
    e = _tiny_engine(page_size=16)
    reports = e.cost_reports()
    assert set(reports) == {"serving.decode", "serving.prefill_chunk",
                            "serving.cow_copy", "serving.kv_export",
                            "serving.kv_import"}
    for name, r in reports.items():
        assert r.available and r.flops is not None, name
        assert r.peak_bytes and r.peak_bytes > 0, name
    # only= restricts pricing (a bench line reports ONE program and
    # must not pay the other entries' compiles)
    assert set(e.cost_reports(only=("serving.decode",))) == \
        {"serving.decode"}
    with pytest.raises(ValueError, match="does not watch"):
        e.cost_reports(only=("serving.spec_verify",))   # spec_k=0 engine
    s = _tiny_engine(paged=False)
    assert set(s.cost_reports()) == {"serving.decode", "serving.prefill"}


@pytest.mark.slow
def test_trainstep_cost_report():
    from paddle_tpu import nn
    from paddle_tpu.jit import TrainStep
    net = nn.Sequential(nn.Linear(8, 8))
    opt = paddle.optimizer.AdamW(parameters=net.parameters(),
                                 learning_rate=1e-3)
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(), opt)
    x = jnp.ones((2, 8), jnp.float32)
    r = step.cost_report((x, x))
    assert r.name == "jit.train_step" and r.available
    assert r.flops and r.flops > 0 and r.peak_bytes > 0


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------

def test_hbm_disarmed_path_is_one_global_check():
    """Acceptance: the disabled-path cost is ONE module-global None check
    (registry noop-identity discipline) — no ledger object exists, the
    boundary hooks return immediately, nothing touches jax."""
    assert hbm.active() is None
    assert hbm.maybe_sample() is None
    assert hbm.sample() is None
    assert hbm.counter_marks() == []


def test_hbm_ledger_samples_gauges_and_marks():
    e = _tiny_engine(page_size=16)
    led = hbm.enable()
    try:
        s = led.sample("test")
        assert s["devices"], "no per-device live bytes collected"
        assert s["live_bytes_total"] > 0
        # the registered engine's pool is priced into the gauge
        assert s["kv_pool_bytes"] >= e.kv_pool_bytes()
        g = obs.gauge("hbm.kv_pool_bytes")
        assert g.value == s["kv_pool_bytes"]
        dev = next(iter(s["devices"]))
        assert obs.gauge("hbm.live_bytes", ("device",)).labels(
            device=dev).value == pytest.approx(s["devices"][dev])
        assert led.marks(), "no chrome counter marks buffered"
        st = hbm.ledger_state()
        assert st["armed"] and st["top_arrays"]
        big = st["top_arrays"][0]
        assert big["nbytes"] > 0 and big["count"] >= 1
        assert st["last_sample"]["tag"] == "test"
    finally:
        hbm.disable()


def test_hbm_stale_device_gauges_zeroed(monkeypatch):
    """A device whose arrays were all deleted must read 0 on the next
    sample — a stale per-device gauge would contradict ledger_state()
    in the exact OOM post-mortem the ledger exists for."""
    led = hbm.enable()
    try:
        monkeypatch.setattr(hbm, "_live_per_device",
                            lambda: {"devA": 100.0})
        led.sample()
        g = obs.gauge("hbm.live_bytes", ("device",))
        assert g.labels(device="devA").value == 100.0
        monkeypatch.setattr(hbm, "_live_per_device",
                            lambda: {"devB": 50.0})
        led.sample()
        assert g.labels(device="devA").value == 0.0
        assert g.labels(device="devB").value == 50.0
        # the zeroing is marked once, not re-marked every later sample
        led.sample()
        zero_marks = [m for m in led.marks()
                      if m[0] == "hbm.live_bytes{device=devA}"
                      and m[2] == 0.0]
        assert len(zero_marks) == 1
    finally:
        hbm.disable()


def test_hbm_maybe_sample_thinning():
    led = hbm.enable(sample_every=3)
    try:
        assert led.maybe_sample() is None       # 1
        assert led.maybe_sample() is None       # 2
        assert led.maybe_sample() is not None   # 3: fires
        assert led.maybe_sample() is None       # 4
    finally:
        hbm.disable()


def test_hbm_scheduler_iteration_boundary_sampling():
    from paddle_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                              Request)
    e = _tiny_engine(page_size=16)
    led = hbm.enable()
    try:
        sched = ContinuousBatchingScheduler(e)
        rng = np.random.default_rng(0)
        sched.submit(Request(prompt=rng.integers(0, 64, (8,)),
                             max_new_tokens=3, temperature=0.0))
        sched.run()
        assert led.last, "no sample taken at an iteration boundary"
        assert led.last["tag"] == "serving.iteration"
        assert led.last["kv_pool_bytes"] >= e.kv_pool_bytes()
    finally:
        hbm.disable()


def test_hbm_restore_transient_gauge(tmp_path):
    from paddle_tpu.incubate.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": np.ones((32, 32), np.float32)}, wait=True)
    mgr.close()
    g = obs.gauge("hbm.restore_transient_bytes")
    seen = {}
    orig = hbm.clear_restore

    def spy():
        seen["during"] = g.value     # gauge while the tree is held
        orig()

    hbm.clear_restore = spy
    try:
        CheckpointManager(str(tmp_path)).restore()
    finally:
        hbm.clear_restore = orig
    assert seen["during"] >= 32 * 32 * 4
    assert g.value == 0.0            # cleared after placement


def test_hbm_marks_land_in_chrome_export(tmp_path):
    from paddle_tpu.observability import tracing
    led = hbm.enable()
    live = jnp.ones((8,), jnp.float32)    # a lane needs one live array
    try:
        led.sample("chrome")
        del live
        tr = tracing.Tracer()
        tr.add_span("decode", 1000, 2000, trace_id=1)
        out = tmp_path / "chrome.json"
        tracing.write_chrome(str(out), tr.spans(), tr.instants(),
                             include_profiler=False)
        doc = json.loads(out.read_text())
        counters = [ev for ev in doc["traceEvents"]
                    if ev.get("ph") == "C" and ev.get("cat") == "hbm"]
        assert counters, "no HBM counter lanes in the chrome export"
        names = {ev["name"] for ev in counters}
        assert "hbm.kv_pool_bytes" in names
        assert any(n.startswith("hbm.live_bytes") for n in names)
    finally:
        hbm.disable()


# ---------------------------------------------------------------------------
# bench schema: cost block + trajectory cost cursors
# ---------------------------------------------------------------------------

def _bench_schema():
    import importlib.util
    import pathlib
    p = pathlib.Path(__file__).resolve().parent.parent / "tools" \
        / "bench_schema.py"
    spec = importlib.util.spec_from_file_location("bench_schema_c", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_OK_COST = {"flops": 1e9, "hbm_bytes": 1e8, "peak_bytes": 1000,
            "mfu": 0.4, "bw_util": 0.6}


def test_schema_validates_cost_block():
    bs = _bench_schema()
    line = {"metric": "m", "value": 1.0, "unit": "x", "cost": dict(_OK_COST)}
    bs.validate_line(line, "<t>")
    # nulls are legal everywhere (CPU smoke shape)
    line["cost"] = {k: None for k in _OK_COST}
    bs.validate_line(line, "<t>")
    # --expect-cost requires the block
    with pytest.raises(bs.SchemaError, match="no 'cost' block"):
        bs.validate_line({"metric": "m", "value": 1.0, "unit": "x"},
                         "<t>", expect_cost=True)
    for bad in (
        {k: v for k, v in _OK_COST.items() if k != "mfu"},   # missing key
        dict(_OK_COST, peak_bytes=-5),                       # negative
        dict(_OK_COST, mfu="fast"),                          # non-number
        dict(_OK_COST, bw_util=7.0),                         # implausible
    ):
        with pytest.raises(bs.SchemaError):
            bs.validate_line({"metric": "m", "value": 1.0, "unit": "x",
                              "cost": bad}, "<t>")


def _traj_cost_entry(tmp_path, name, value, backend, cost=None,
                     layout="paged"):
    line = {"metric": "decode_tokens_per_sec", "value": value,
            "unit": "tok/s", "cache_layout": layout,
            "config": {"backend": backend, "model": "tiny"}}
    if cost is not None:
        line["cost"] = cost
    p = tmp_path / name
    p.write_text(json.dumps({"n": 1, "cmd": "bench", "rc": 0,
                             "parsed": line}))
    return str(p)


def test_trajectory_rejects_peak_hbm_regression(tmp_path):
    """Acceptance: the trajectory gate rejects a synthetic >5% peak-HBM
    growth between like-for-like on-chip entries."""
    bs = _bench_schema()
    ok = [
        _traj_cost_entry(tmp_path, "BENCH_decode_r01.json", 100.0, "tpu",
                         dict(_OK_COST, peak_bytes=1000)),
        _traj_cost_entry(tmp_path, "BENCH_decode_r02.json", 100.0, "tpu",
                         dict(_OK_COST, peak_bytes=1040)),   # +4%: fine
    ]
    assert bs.check_trajectory(ok) == []
    grown = ok + [
        _traj_cost_entry(tmp_path, "BENCH_decode_r03.json", 100.0, "tpu",
                         dict(_OK_COST, peak_bytes=1100)),   # +5.8%
    ]
    fails = bs.check_trajectory(grown)
    assert len(fails) == 1 and "peak HBM grew" in fails[0]
    assert "BENCH_decode_r03" in fails[0] and "BENCH_decode_r02" in fails[0]


def test_trajectory_rejects_mfu_drop_and_skips_cpu(tmp_path):
    bs = _bench_schema()
    paths = [
        _traj_cost_entry(tmp_path, "BENCH_decode_r11.json", 100.0, "tpu",
                         dict(_OK_COST, mfu=0.40)),
        _traj_cost_entry(tmp_path, "BENCH_decode_r12.json", 100.0, "tpu",
                         dict(_OK_COST, mfu=0.38)),          # -5% MFU
    ]
    fails = bs.check_trajectory(paths)
    assert len(fails) == 1 and "MFU fell" in fails[0]
    # CPU entries carry null utilizations and never cost-gate
    cpu = [
        _traj_cost_entry(tmp_path, "BENCH_decode_r21.json", 100.0, "cpu",
                         {k: None for k in _OK_COST}),
        _traj_cost_entry(tmp_path, "BENCH_decode_r22.json", 1.0, "cpu",
                         {k: None for k in _OK_COST}),
    ]
    assert bs.check_trajectory(cpu) == []
    # a pre-cost chip line anchors tokens/s but not the cost cursors
    legacy = [
        _traj_cost_entry(tmp_path, "BENCH_decode_r31.json", 100.0, "tpu"),
        _traj_cost_entry(tmp_path, "BENCH_decode_r32.json", 99.0, "tpu",
                         dict(_OK_COST)),
    ]
    assert bs.check_trajectory(legacy) == []
    # ...and, crucially, a cost-LESS chip line in the middle must not
    # RESET the anchor: the cost cursor compares against the last entry
    # that carried a cost, so the drop across the gap still fails
    gap = [
        _traj_cost_entry(tmp_path, "BENCH_decode_r41.json", 100.0, "tpu",
                         dict(_OK_COST, mfu=0.40)),
        _traj_cost_entry(tmp_path, "BENCH_decode_r42.json", 100.0, "tpu"),
        _traj_cost_entry(tmp_path, "BENCH_decode_r43.json", 100.0, "tpu",
                         dict(_OK_COST, mfu=0.10)),
    ]
    fails = bs.check_trajectory(gap)
    assert len(fails) == 1 and "MFU fell" in fails[0]
    assert "BENCH_decode_r43" in fails[0] and "BENCH_decode_r41" in fails[0]
    # a PARTIAL cost block (peak present, mfu null — a chip whose part
    # is missing from the peak table) must not displace the MFU anchor
    # either: each cost metric keeps its own last-carrying cursor
    partial = [
        _traj_cost_entry(tmp_path, "BENCH_decode_r51.json", 100.0, "tpu",
                         dict(_OK_COST, mfu=0.40, peak_bytes=1000)),
        _traj_cost_entry(tmp_path, "BENCH_decode_r52.json", 100.0, "tpu",
                         dict(_OK_COST, mfu=None, peak_bytes=1010)),
        _traj_cost_entry(tmp_path, "BENCH_decode_r53.json", 100.0, "tpu",
                         dict(_OK_COST, mfu=0.10, peak_bytes=1015)),
    ]
    fails = bs.check_trajectory(partial)
    assert len(fails) == 1 and "MFU fell" in fails[0]
    assert "BENCH_decode_r53" in fails[0] and "BENCH_decode_r51" in fails[0]


def test_trajectory_cost_cursor_is_like_for_like(tmp_path):
    """A slotted line's cost must not anchor the paged cursor: the cost
    cursors ride the SAME (model, layout, kv_dtype, spec) key as the
    tokens/s gate."""
    bs = _bench_schema()
    paths = [
        _traj_cost_entry(tmp_path, "BENCH_decode_r41.json", 100.0, "tpu",
                         dict(_OK_COST, peak_bytes=500), layout="slotted"),
        _traj_cost_entry(tmp_path, "BENCH_decode_r42.json", 100.0, "tpu",
                         dict(_OK_COST, peak_bytes=1000), layout="paged"),
        _traj_cost_entry(tmp_path, "BENCH_decode_r43.json", 100.0, "tpu",
                         dict(_OK_COST, peak_bytes=1020), layout="paged"),
    ]
    assert bs.check_trajectory(paths) == []
