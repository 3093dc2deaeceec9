"""``benchmarks/lib/scopes.py`` without a chip: self time on hand-made
traces, the by-scope sum over the recorded chip trace with a made-up index,
the join with the index a compiled CPU program really gives, and the
readers PR 25 added on nothing, on a parent's registry and on a registry
that holds the compile series."""
import gc
import json
import os

import pytest

from benchmarks.lib import harness, scopes as S, trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_READERS = ["optimizer_ms.train", "attention_ms.train",
                  "mlp_ms.train", "head_loss_ms.train",
                  "scope_coverage_pct.train"]
REGISTRY_READERS = ["entry_trace_s", "entry_lower_s", "entry_load_s",
                    "unwatched_programs", "pkg_import_s"]


def test_instruction_name():
    assert S.instruction_name(
        "%fusion.263 = (f32[8]{0}) fusion(...), kind=kLoop") == "fusion.263"
    assert S.instruction_name("%flash_fwd.4 = (...), custom_call_target="
                              '"tpu_custom_call"') == "flash_fwd.4"
    assert S.instruction_name("copy-done.3") == "copy-done.3"


@pytest.mark.parametrize("ops,own", [
    # an enclosing event gives its children's time away
    ([["while", 0, 100], ["a", 10, 10], ["b", 30, 10]], [80, 10, 10]),
    # nesting two deep, and a sibling after the parent has ended
    ([["p", 0, 50], ["c", 10, 30], ["g", 20, 5], ["s", 60, 10]],
     [20, 25, 5, 10]),
    # a partial overlap (an async pair's tail): no instant is counted twice
    ([["a", 0, 10], ["b", 5, 15]], [5, 15]),
    # the same start: the longer encloses the shorter
    ([["short", 0, 4], ["long", 0, 10]], [4, 6]),
    # a zero-length event, and idle gaps on both sides
    ([["x", 5, 0], ["y", 7, 3], ["z", 20, 2]], [0, 3, 2]),
    ([], []),
])
def test_self_time_equals_the_union(ops, own):
    assert S.self_times(ops) == own
    assert sum(own) * 1e-9 == pytest.approx(T.union_seconds(ops))


def hand_made():
    # two executions of jit_step_fn; in each a while encloses two bodies,
    # then a flash call, then an update fusion; one event of another program
    ops = []
    for base in (0, 2000):
        ops += [["%while.1 = (...) while(...)", base, 600],
                ["%fusion.7 = f32[8]{0} fusion(...)", base + 50, 200],
                ["%fusion.8 = f32[8]{0} fusion(...)", base + 300, 250],
                ['%flash_fwd.2 = (...), custom_call_target="tpu_custom_call"',
                 base + 700, 300],
                ["%multiply_fusion.3 = f32[8]{0} fusion(...)", base + 1000,
                 400]]
    ops.append(["%fusion.7 = f32[8]{0} fusion(...)", 1500, 100])
    ops.sort(key=lambda e: e[1])
    modules = [["jit_step_fn(1)", 0, 1400], ["jit_other(2)", 1500, 100],
               ["jit_step_fn(1)", 2000, 1400]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": []}


INDEX = {"jit_step_fn": {"while.1": None, "fusion.7": "mlp",
                         "fusion.8": "attn", "flash_fwd.2": "attn",
                         "multiply_fusion.3": "optimizer"}}


def test_scope_ms_per_execution():
    got = S.scope_ms(hand_made(), "step_fn", INDEX)
    assert got == pytest.approx({"unscoped": 150e-6, "mlp": 200e-6,
                                 "attn": 550e-6, "optimizer": 400e-6})
    # the other program's fusion.7 is not the step's
    assert sum(got.values()) == pytest.approx(1300e-6)
    assert S.scope_ms(hand_made(), "absent", INDEX) is None
    assert S.scope_ms(hand_made(), "other", INDEX) is None


def test_recorded_trace_sums_to_the_busy_time():
    """PR 23's recorded chip trace with a made-up index: the scopes and
    ``unscoped`` sum to the device's busy time inside the two steps."""
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        trace = json.load(f)
    dev = trace["devices"]["/device:TPU:0"]
    roles = ["attn", "mlp", "optimizer", None]
    table = {}
    for text, _, _ in dev["ops"]:
        name = S.instruction_name(text)
        table[name] = ("attn" if T.MOSAIC_MARK in text
                       else roles[len(name) % len(roles)])
    got = S.scope_ms(trace, "step_fn", {"jit_step_fn": table})
    runs = T._runs_of(dev, "step_fn")
    inside = [e for e in dev["ops"]
              if any(s <= e[1] < s + d for _, s, d in runs)]
    busy_ms = T.union_seconds(inside) * 1e3 / len(runs)
    assert sum(got.values()) == pytest.approx(busy_ms, rel=1e-9)
    assert S.UNSCOPED in got and got["attn"] >= T.mosaic_ms_per_module(
        trace, "step_fn")


def test_the_join_with_a_compiled_programs_own_index():
    """A trace made from the instruction names of the tiny train step's
    compiled program, read through the index the program publishes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       GPTPretrainingCriterion)
    from paddle_tpu.observability import scopes as program_scopes
    # a fresh compile: a cache filled before the scopes existed has none
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig.tiny())
        crit = GPTPretrainingCriterion()
        step = TrainStep(
            model, lambda logits, labels: crit(logits, labels),
            paddle.optimizer.AdamW(parameters=model.parameters(),
                                   learning_rate=1e-4))
        x = jnp.zeros((2, 32), jnp.int32)
        step(x, x)
        table = step._step.instruction_scopes()["jit_step_fn"]
        del step
        gc.collect()        # the readers come after the step has gone
        ops = [["%%%s = f32[8]{0} fusion(...)" % name, 10 * i, 10]
               for i, name in enumerate(table)]
        trace = {"devices": {"/device:TPU:0": {
            "ops": ops, "modules": [["jit_step_fn(7)", 0, 10 * len(ops)]]}},
            "host": []}
        got = S.program_scope_ms(trace, "step_fn")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    assert set(program_scopes.TRAIN) | {S.UNSCOPED} == set(got)
    assert sum(got.values()) == pytest.approx(10e-6 * len(ops))
    record = {"kind": "train"}
    for name, roles in (("optimizer_ms.train", ["optimizer"]),
                        ("attention_ms.train", ["attn"]),
                        ("mlp_ms.train", ["mlp"]),
                        ("head_loss_ms.train", ["embed", "lm_head", "loss"])):
        assert harness.layer_reader(name)({}, trace, record) == pytest.approx(
            sum(got[r] for r in roles))
    assert harness.layer_reader("scope_coverage_pct.train")(
        {}, trace, record) == pytest.approx(
            100 * (1 - got[S.UNSCOPED] / sum(got.values())))


@pytest.mark.parametrize("name", DEVICE_READERS + REGISTRY_READERS)
def test_a_new_reader_with_nothing_to_read_returns_nothing(name):
    read = harness.layer_reader(name)
    assert read({}, None, {"kind": "train", "rehearsal": True}) is None
    # a CPU rehearsal's trace holds no device; a serving run is not a step
    assert read({}, {"devices": {}, "host": []}, {"kind": "train"}) is None
    if name in DEVICE_READERS:
        assert read({}, hand_made(), {"kind": "serve_open"}) is None


def test_device_readers_survive_a_program_without_an_index(monkeypatch):
    """On the parent commit ``paddle_tpu.observability.scopes`` does not
    exist; a program may also fail to give its index.  Neither raises."""
    import paddle_tpu.observability.scopes as program_scopes

    def boom():
        raise RuntimeError("no index today")
    monkeypatch.setattr(program_scopes, "index", boom)
    for name in DEVICE_READERS:
        assert harness.layer_reader(name)(
            {}, hand_made(), {"kind": "train"}) is None


SNAPSHOT = {
    "compile.phase_seconds": {"type": "counter", "labels": ["entry", "phase"],
                              "series": [
        {"labels": {"entry": "jit.train_step", "phase": "trace"},
         "value": 14.5},
        {"labels": {"entry": "jit.train_step", "phase": "lower"},
         "value": 3.25},
        {"labels": {"entry": "jit.train_step", "phase": "backend"},
         "value": 2.0},
        {"labels": {"entry": "serving.decode", "phase": "trace"},
         "value": 0.5},
        {"labels": {"entry": "(unwatched)", "phase": "trace"},
         "value": 9.0},
        {"labels": {"entry": "(unwatched)", "phase": "backend"},
         "value": 4.0}]},
    "compile.cache": {"type": "counter", "labels": ["entry", "result"],
                      "series": [
        {"labels": {"entry": "(unwatched)", "result": "hit"}, "value": 50.0},
        {"labels": {"entry": "(unwatched)", "result": "miss"}, "value": 7.0},
        {"labels": {"entry": "jit.train_step", "result": "hit"},
         "value": 1.0}]},
    "process.import_seconds": {"type": "gauge", "labels": [],
                               "series": [{"labels": {}, "value": 6.5}]},
}


@pytest.mark.parametrize("name,value", [
    ("entry_trace_s", 15.0), ("entry_lower_s", 3.25), ("entry_load_s", 2.0),
    ("unwatched_programs", 57.0), ("pkg_import_s", 6.5)])
def test_registry_readers(name, value):
    read = harness.layer_reader(name)
    assert read(SNAPSHOT, None, {"kind": "train"}) == pytest.approx(value)
    # a parent's registry holds the older series only
    assert read({"compile.count": {"type": "counter", "labels": ["entry"],
                                   "series": []}}, None,
                {"kind": "train"}) is None
