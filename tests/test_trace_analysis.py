"""tpu-audit (paddle_tpu.analysis.trace) — tier-1 gate.

Mirrors tests/test_static_analysis.py one tier down: (1) pin each TPU5xx
pass's detection on seeded fixture programs (exact rule + program +
op-path), (2) run the full canonical-program registry strict so any new
trace-level violation fails CI, (3) prove the TPU504 estimator rejects a
VMEM-oversized autotune candidate BEFORE compile.
"""
import glob
import importlib.util
import json
import os

import pytest

from paddle_tpu.analysis import F32_ACCUM_OPS, TRACE_RULES
from paddle_tpu.analysis.trace import (TraceAnalyzer, TraceProgram,
                                       build_programs, fits_vmem,
                                       pallas_footprints, walk_eqns)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(REPO, "tests", "analysis_fixtures", "trace")


def _fixture_programs():
    programs = []
    for path in sorted(glob.glob(os.path.join(FIXDIR,
                                              "tpu5*_programs.py"))):
        name = "trace_fixture_" + os.path.basename(path)[:-3]
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        programs.extend(mod.build_programs())
    return programs


@pytest.fixture(scope="module")
def fixture_report():
    an = TraceAnalyzer(root=REPO, baseline_path=None)
    return an.run(_fixture_programs())


def test_rule_catalogue():
    assert set(TRACE_RULES) == {"TPU501", "TPU502", "TPU503", "TPU504",
                                "TPU505", "TPU506"}


def test_fixture_matrix(fixture_report):
    """Each seeded fixture trips exactly its rule at a pinned op path; the
    negative fixtures trip nothing."""
    by = {}
    for f in fixture_report.findings:
        by.setdefault(f.path, []).append((f.rule, f.symbol))

    assert sorted(by["fixture/tpu501_bad"]) == [
        ("TPU501", "convert_element_type.0"),   # tanh on an upcast
        ("TPU501", "convert_element_type.1"),   # f32 matmul of upcasts
    ]
    assert by["fixture/tpu502_donation_miss"] == [
        ("TPU502", "in[0]:params/w")]
    assert by["fixture/tpu503_branch_mismatch"] == [("TPU503", "cond.0")]
    assert by["fixture/tpu503_bad_perm"] == [("TPU503", "ppermute.0")]
    assert by["fixture/tpu503_undeclared_axis"] == [
        ("TPU503", "shard_map.0")]
    assert by["fixture/tpu504_oversized"] == [("TPU504", "pallas_call.0")]
    assert by["fixture/tpu506_over_budget"] == [
        ("TPU506", "memory/peak_bytes")]
    # a budgeted program that cannot be priced is LOUD, never a skip
    assert by["fixture/tpu506_unpriceable"] == [
        ("TPU506", "memory/peak_bytes")]
    dirty = sorted(by["fixture/tpu505_dirty"])
    assert ("TPU505", "debug_print.0") in dirty
    assert ("TPU505", "dot_general.0") in dirty     # dead matmul
    assert ("TPU505", "dot_general.2") in dirty     # duplicate matmul
    # callbacks allowed -> only the dead/dup findings remain
    allowed = {r for r, _s in by["fixture/tpu505_callbacks_allowed"]}
    assert allowed == {"TPU505"}
    assert not any(s.startswith(("debug_callback", "debug_print"))
                   for _r, s in by["fixture/tpu505_callbacks_allowed"])
    # negatives are silent
    for neg in ("fixture/tpu501_ok", "fixture/tpu501_unscoped",
                "fixture/tpu502_ok", "fixture/tpu503_ok",
                "fixture/tpu504_ok", "fixture/tpu505_ok",
                "fixture/tpu506_ok"):
        assert neg not in by, by.get(neg)


def test_finding_messages_carry_rationale(fixture_report):
    msgs = {f.rule: f.message for f in fixture_report.findings}
    assert "statistics/accumulators" in msgs["TPU501"]
    assert "HBM" in msgs["TPU502"]
    assert "deadlock" in msgs["TPU503"] or "axis" in msgs["TPU503"]
    assert "VMEM" in msgs["TPU504"]
    assert "budget" in msgs["TPU506"]


def test_trace_baseline_roundtrip(tmp_path):
    """(rule, program, op-path) baseline entries suppress trace findings;
    unmatched entries surface as stale."""
    bl = tmp_path / "baseline.txt"
    bl.write_text(
        "TPU502 fixture/tpu502_donation_miss::in[0]:params/w"
        "  # fixture: accepted for the baseline test\n"
        "TPU501 no/such/program::convert_element_type.9  # never matches\n"
        # an AST-tier entry must NOT be reported stale by a trace run
        "TPU101 paddle_tpu/somefile.py::fn  # other tier's debt\n")
    an = TraceAnalyzer(root=REPO, baseline_path=str(bl))
    report = an.run(_fixture_programs())
    assert not any(f.path == "fixture/tpu502_donation_miss"
                   for f in report.findings)
    assert any(f.path == "fixture/tpu502_donation_miss"
               for f in report.baselined)
    assert len(report.stale_baseline) == 1
    assert "TPU501" in report.stale_baseline[0]


def test_walk_eqns_paths_are_unique():
    progs = [p for p in _fixture_programs()
             if p.name == "fixture/tpu505_dirty"]
    paths = [s.path for s in walk_eqns(progs[0].jaxpr)]
    assert len(paths) == len(set(paths))
    assert any(p.startswith("dot_general.") for p in paths)


def test_vmem_estimator_prices_blocks_and_scratch():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref, scr, sem):
        o_ref[...] = x_ref[...]

    def call(x):
        return pl.pallas_call(
            kernel, grid=(4,),
            in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((512, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((2, 128, 128), jnp.bfloat16),
                            pltpu.SemaphoreType.DMA((2,))],
        )(x)

    jx = jax.make_jaxpr(call)(jax.ShapeDtypeStruct((512, 128),
                                                   jnp.float32))
    (fp,) = pallas_footprints(jx, "t")
    # in + out blocks double-buffered: 2 * 128*128*4 * 2 = 256 KiB
    assert fp.operand_bytes == 2 * 128 * 128 * 4 * 2
    # VMEM scratch counted once, semaphore free: 2*128*128*2 = 64 KiB
    assert fp.scratch_bytes == 2 * 128 * 128 * 2
    assert fp.fits()


def test_any_space_operands_not_counted():
    """ANY-memory operands stay in HBM (their kernels DMA through counted
    scratch) — the pipelined flash variant depends on this pricing."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, big_ref, o_ref):
        o_ref[...] = x_ref[...]

    def call(x, big):
        return pl.pallas_call(
            kernel, grid=(4,),
            in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0)),
                      pl.BlockSpec(memory_space=pltpu.ANY)],
            out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((512, 128), jnp.float32),
        )(x, big)

    sds = jax.ShapeDtypeStruct
    jx = jax.make_jaxpr(call)(sds((512, 128), jnp.float32),
                              sds((8192, 8192), jnp.float32))  # 256 MB
    (fp,) = pallas_footprints(jx, "t")
    assert fp.fits(), fp.summary()   # the ANY operand priced nothing


def test_autotune_rejects_oversized_candidate_before_compile(monkeypatch):
    """TPU504 wired into tune(): the unfittable candidate is rejected from
    the timing table without its runner (= compile) ever being built."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from paddle_tpu.kernels import autotune as at

    def _mk(block, interpret):
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        def fn(x):
            return pl.pallas_call(
                kernel, grid=(4,),
                in_specs=[pl.BlockSpec((block, block), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((block, block), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((block * 4, block),
                                               jnp.float32),
                interpret=interpret,
            )(x)
        return fn

    compiled = []

    def candidates(key):
        return [{"variant": "small", "config": {"block": 128}},
                {"variant": "huge", "config": {"block": 4096}}]

    def runner(cand, key):
        compiled.append(cand["variant"])     # building = compiling
        block = cand["config"]["block"]
        fn = jax.jit(_mk(block, True))
        import numpy as np
        x = jnp.asarray(np.zeros((block * 4, block), np.float32))

        def run():
            jax.block_until_ready(fn(x))
        return run

    def traceable(cand, key):
        block = cand["config"]["block"]
        return _mk(block, True), (jax.ShapeDtypeStruct(
            (block * 4, block), jnp.float32),)

    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_SAMPLES", "1")
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE", "")
    at.register_family("_test_vmem_gate", candidates, runner,
                       traceable=traceable)
    try:
        cand = at.tune("_test_vmem_gate", {"shape": "x"}, persist=False)
    finally:
        at._FAMILIES.pop("_test_vmem_gate", None)
    assert cand["variant"] == "small"
    # the oversized candidate was never built/compiled — rejection
    # happened at the static estimate, before its runner existed
    assert compiled == ["small"]

    # when EVERY candidate is statically rejected, tune() must fail loud
    # instead of persisting a default the gate just proved faults
    at.register_family(
        "_test_vmem_all_rejected",
        lambda key: [{"variant": "huge", "config": {"block": 4096}}],
        runner, traceable=traceable)
    try:
        with pytest.raises(ValueError, match="no candidate fits"):
            at.tune("_test_vmem_all_rejected", {"shape": "x"},
                    persist=False)
    finally:
        at._FAMILIES.pop("_test_vmem_all_rejected", None)
    assert compiled == ["small"]   # still nothing else compiled


def test_registry_builds_and_is_strict_green():
    """THE gate: the canonical-program registry audits green (modulo the
    reasoned baseline) — every future perf/robustness PR is checked
    against these programs."""
    programs, skipped, errors = build_programs()
    assert not errors, errors
    names = {p.name for p in programs}
    assert "gpt_train_step" in names
    assert "gpt_decode" in names
    assert "pipeline_1f1b" in names, skipped   # conftest forces 8 devices
    assert any(n.startswith("pallas/flash_fwd/") for n in names)
    assert any(n.startswith("pallas/flash_bwd/") for n in names)
    assert any(n.startswith("pallas/decode_attn/") for n in names)
    # every registered flash VARIANT is a program
    for v in ("base", "bf16chain", "iotafree", "pipelined"):
        assert "pallas/flash_fwd/%s" % v in names
    an = TraceAnalyzer(root=REPO)
    report = an.run(programs, errors=errors)
    assert report.ok, "new tpu-audit findings:\n" + \
        "\n".join(f.format() for f in report.findings)
    assert not report.stale_baseline, report.stale_baseline
    assert report.baselined, "the reasoned TPU505 baseline should match"


def test_registry_donations_materialize():
    """TPU502 positively verifies the TrainStep/pipeline donations: the
    lowered entries carry aliasing/donor marks for every donated input
    (the pass being silent must mean 'checked and green', not
    'nothing to check')."""
    from paddle_tpu.analysis.trace.donation import (declared_donations,
                                                    parse_entry_aliasing)
    programs, _, errors = build_programs(["gpt_train_step",
                                          "pipeline_1f1b"])
    assert not errors, errors
    checked = 0
    for p in programs:
        donated = declared_donations(p)
        assert donated and any(donated), p.name
        entry = parse_entry_aliasing(p.lowered_text)
        assert entry is not None and len(entry) == len(donated), p.name
        for i, don in enumerate(donated):
            if don:
                info = entry[i]
                assert info["aliased"] or (info["donor"]
                                           and info["result_match"]), \
                    (p.name, i, info)
                checked += 1
    assert checked > 10   # the GPT step donates its whole param tree


def test_cli_trace_mode(tmp_path, capsys):
    from paddle_tpu.analysis.__main__ import main

    # pattern-filtered trace run, strict, text format
    rc = main(["fixture-nothing-matches*", "--trace", "--root", REPO,
               "-q"])
    # zero programs matched -> operational error, not silent green
    assert rc == 2

    rc = main(["pallas/decode_attn/*", "--trace", "--root", REPO, "--strict",
               "-q"])
    assert rc == 0

    # JSON format is machine-readable and carries the findings
    rc = main(["pallas/decode_attn/*", "--trace", "--root", REPO,
               "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["ok"] and doc["files"] >= 1
    assert doc["findings"] == []


def test_cli_select_and_github_format(capsys):
    from paddle_tpu.analysis.__main__ import main

    # --select with a trace rule id runs only that pass
    rc = main(["pallas/decode_attn/*", "--trace", "--select", "TPU504",
               "--root", REPO, "--strict", "-q"])
    assert rc == 0
    capsys.readouterr()
    # unknown rule id still errors
    rc = main(["--trace", "--select", "TPU999", "--root", REPO])
    assert rc == 2
    capsys.readouterr()

    # github format on the AST tier: violations print ::error lines
    bad = os.path.join(REPO, "tests", "analysis_fixtures", "x64_bad.py")
    rc = main([bad, "--root", REPO, "--baseline", "none",
               "--format", "github", "--strict", "-q"])
    out = capsys.readouterr().out
    assert rc == 1
    assert out.startswith("::error file=")
    assert "TPU201" in out


def test_f32_accum_allowlist_is_shared():
    """The static TPU501 vocabulary is importable from the package root —
    the runtime/kernels side references the same set (the S64_COMPUTE_OPS
    sharing pattern)."""
    assert "reduce_sum" in F32_ACCUM_OPS and "exp" in F32_ACCUM_OPS
    assert "dot_general" not in F32_ACCUM_OPS
    assert "tanh" not in F32_ACCUM_OPS
