"""The trace reduction, on a hand-made trace (exact values) and on a small
trace recorded on the chip (PR 23's builder run, cut to two steps)."""
import json
import os

import pytest

from benchmarks.lib import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
MOSAIC = ('%jvp__.1 = (bf16[2,8,8]{2,1,0}) custom-call(...), '
          'custom_call_target="tpu_custom_call"')
CONCAT = ('%custom-call.254 = f32[8,8]{1,0} custom-call(...), '
          'custom_call_target="ConcatBitcast"')


def hand_made():
    # two executions of jit_step_fn, 1000 ns each, 100 ns apart; inside each:
    # a fusion, a Mosaic call and a ConcatBitcast that a fusion overlaps
    ops = []
    for base in (0, 1100):
        ops += [["%fusion.7 = f32[8]{0} fusion(...)", base + 0, 300],
                [CONCAT, base + 300, 1],
                ["%convolution_add_fusion.47 = bf16[8]{0} fusion(...)",
                 base + 300, 200],
                [MOSAIC, base + 600, 250],
                ["%fusion.9 = f32[8]{0} fusion(...)", base + 900, 100]]
    modules = [["jit_step_fn(123)", 0, 1000], ["jit_step_fn(123)", 1100, 1000],
               ["jit_convert_element_type(9)", 1050, 10]]
    host = [["bench.step_call", 0, 400], ["bench.step_wait", 450, 700]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_busy_union_and_idle_share():
    tr = hand_made()
    # per execution: [0,500) + [600,850) + [900,1000) = 850 ns busy
    busy, window = T.busy_and_window(tr)
    assert busy == pytest.approx(1700e-9)
    assert window == pytest.approx(2100e-9)
    assert T.idle_share_pct(tr) == pytest.approx(100 * (1 - 1700 / 2100))


def test_union_merges_overlaps_and_nesting():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 2, 3], ["d", 30, 5]]
    assert T.union_seconds(ev) == pytest.approx(20e-9)
    assert T.union_seconds([]) == 0.0


def test_mosaic_sum_leaves_other_custom_calls_out():
    tr = hand_made()
    assert T.mosaic_ms_per_module(tr, "step_fn") == pytest.approx(250e-6)
    assert T.mosaic_ms_per_module(tr, "absent") is None


def test_module_median_and_summary():
    tr = hand_made()
    assert T.module_median_ms(tr, "step_fn") == pytest.approx(1000e-6)
    assert T.module_median_ms(tr, "decode_fn") is None
    # a prefix of another program's name is not that program
    assert T.module_median_ms(tr, "step") is None
    assert T.module_summary(tr)["jit_step_fn"][0] == 2


def test_breakdown_groups_and_labels():
    tr = hand_made()
    top = dict(T.top_device_ops(tr))
    assert top["fusion"] == pytest.approx(800e-9)
    assert top["jvp__"] == pytest.approx(500e-9)
    assert top["convolution_add_fusion"] == pytest.approx(400e-9)
    gaps = dict(T.idle_gaps(tr))
    # gaps: [500,600) and [850,900) under step_wait, [1000,1100) likewise,
    # [1600,1700) and [1950,2000) under no span of the benchmark's
    assert gaps["bench.step_wait"] == pytest.approx(250e-9)
    assert gaps["unattributed"] == pytest.approx(150e-9)


@pytest.mark.parametrize("text,group", [
    ("%fusion.263 = bf16[8]{0} fusion(...)", "fusion"),
    ("%divide_subtract_fusion.12.3 = f32[] fusion(...)",
     "divide_subtract_fusion"),
    ("%copy-done = u32[2]{0} copy-done(...)", "copy-done"),
    ("%transpose_jvp___.5 = (bf16[1]) custom-call(...)", "transpose_jvp___"),
])
def test_op_group(text, group):
    assert T.op_group(text) == group


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_flash_time(recorded):
    """PR 23's reader matched ConcatBitcast (1 ns each) and read 0.0002 ms;
    the kernels are the tpu_custom_call events: 48 a step, 80.1 ms."""
    ops = recorded["devices"]["/device:TPU:0"]["ops"]
    assert sum(T.MOSAIC_MARK in o[0] for o in ops) == 96
    assert any("ConcatBitcast" in o[0] for o in ops)
    assert T.mosaic_ms_per_module(recorded, "step_fn") == pytest.approx(
        80.096, abs=0.01)


def test_recorded_trace_step_and_roofline(recorded):
    from benchmarks.lib import flops, harness, peaks
    assert T.module_median_ms(recorded, "step_fn") == pytest.approx(
        312.51, abs=0.01)
    cfg = harness.load_json(harness.BENCH_DIR, "configs",
                            "gpt2-medium.json")["gpt_config"]
    least = flops.flash_least_seconds(cfg, 16, 1024,
                                      peaks.peaks("TPU v5 lite"))
    share = 100 * least["seconds"] * 1e3 / T.mosaic_ms_per_module(
        recorded, "step_fn")
    assert least["bound"] == "compute"
    assert share == pytest.approx(18.3, abs=0.2)
    assert share < 100
