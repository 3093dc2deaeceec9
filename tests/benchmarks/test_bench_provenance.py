"""``benchmarks/lib/provenance.py`` and the five readers of PR 37 without a
chip: the join of a trace with a made-up provenance table, the five entries
of ``BENCHMARK.json`` found by name, the readers on nothing and on a program
that publishes no provenance (the parent), and, on a synthetic trace over
the instruction names of a really compiled CPU step with recomputed blocks,
the five values, the identity that holds them to ``scope_coverage_pct.train``
and the one detail line."""
import collections
import gc
import json

import pytest

from benchmarks.lib import harness, provenance as P, scopes as S

G, N, Q = ("train_gpt2m_s1024", "train_nemo3nano_s8192",
           "train_qwen3next_s16384")
READERS = {"forward_ms.train": ("ms", "lower", [G, N, Q]),
           "backward_ms.train": ("ms", "lower", [G, N, Q]),
           "recompute_ms.train": ("ms", "lower", [N, Q]),
           "scope_resolved_pct.train": ("%", "higher", [G, N, Q]),
           "data_movement_ms.train": ("ms", "lower", [G, N, Q])}
Row = collections.namedtuple("Row", "role phase how moves_only opcode op_name")


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_entry_by_name(name):
    spec = harness.benchmark_spec()
    unit, better, cells = READERS[name]
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "device_trace", "layer": "train step",
                     "moves": "train_tokens_per_s", "workloads": cells}
    for cell in (G, N, Q):
        listed = name in harness.metric_names(spec, "per_layer", cell)
        assert listed == (cell in cells)
    # the layer is one the benchmark already had, and the older entry that
    # reads the instructions' own names alone stays
    older = [m for m in spec["per_layer"] if m["name"] not in READERS]
    assert "train step" in {m["layer"] for m in older}
    assert "scope_coverage_pct.train" in {m["name"] for m in older}
    assert callable(harness.layer_reader(name))


def hand_made():
    # two executions of jit_step_fn: a while that encloses a forward fusion
    # and a recomputed one, a copy in front of a kernel, the kernel, a
    # backward fusion, an update, a copy nobody owns; one event of another
    # program in between
    ops = []
    for base in (0, 2000):
        ops += [["%while.1 = (...) while(...)", base, 600],
                ["%fusion.7 = f32[8]{0} fusion(...)", base + 50, 200],
                ["%fusion.8 = f32[8]{0} fusion(...)", base + 300, 250],
                ["%copy.5 = f32[8]{0} copy(...)", base + 600, 100],
                ['%flash_bwd.2 = (...), custom_call_target="tpu_custom_call"',
                 base + 700, 300],
                ["%multiply_fusion.3 = f32[8]{0} fusion(...)", base + 1000,
                 300],
                ["%copy.6 = f32[8]{0} copy(...)", base + 1300, 50],
                ["%copy.77 = f32[8]{0} copy(...)", base + 1350, 30]]
    ops.append(["%fusion.7 = f32[8]{0} fusion(...)", 1500, 100])
    ops.sort(key=lambda e: e[1])
    modules = [["jit_step_fn(1)", 0, 1400], ["jit_other(2)", 1500, 100],
               ["jit_step_fn(1)", 2000, 1400]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": []}


TABLE = {"jit_step_fn": {
    "while.1": Row(None, None, None, False, "while", None),
    "fusion.7": Row("mlp", "forward", "own", False, "fusion",
                    "jit(step_fn)/jvp(mlp)/mul"),
    "fusion.8": Row("mlp", "recompute", "own", False, "fusion", "x"),
    "copy.5": Row("attn", "backward", "user", True, "copy", None),
    "flash_bwd.2": Row("attn", "backward", "own", False, "custom-call", "x"),
    "multiply_fusion.3": Row("optimizer", "update", "own", False, "fusion",
                             "x"),
    "copy.6": Row(None, "update", None, True, "copy",
                  "jit(step_fn)/" + "a" * 200)}}
# copy.77 is in no table: an instruction the program does not know


def test_step_ms_per_execution():
    got = P.step_ms(hand_made(), "step_fn", TABLE)
    ns = 1e-6
    assert got["ms"] == pytest.approx({
        ("unresolved", "none", None): (150 + 30) * ns,
        ("mlp", "forward", "own"): 200 * ns,
        ("mlp", "recompute", "own"): 250 * ns,
        ("attn", "backward", "user"): 100 * ns,
        ("attn", "backward", "own"): 300 * ns,
        ("optimizer", "update", "own"): 300 * ns,
        ("unresolved", "update", None): 50 * ns})
    # every key sums to the busy time inside the step, the other program's
    # fusion.7 left out: what lib/scopes.py sums to as well
    busy = sum(S.scope_ms(hand_made(), "step_fn", {"jit_step_fn": {}})
               .values())
    assert sum(got["ms"].values()) == pytest.approx(busy) == pytest.approx(
        1380 * ns)
    assert got["moves_only_ms"] == pytest.approx(
        {"attn": 100 * ns, "unresolved": 50 * ns})
    assert [row[0] for row in got["unresolved"]] == ["while", "copy"]
    assert got["unresolved"][0][1:] == [pytest.approx(150 * ns), "while",
                                        None]
    # copy.6 and copy.77 are one group; the group's first opcode and the
    # head of its op_name are shown
    assert got["unresolved"][1][1] == pytest.approx(80 * ns)
    assert got["unresolved"][1][2] == "copy"
    assert len(got["unresolved"][1][3]) == 120
    assert P.step_ms(hand_made(), "absent", TABLE) is None
    assert P.step_ms(hand_made(), "other", TABLE) is None


def test_the_detail_line_is_the_tables():
    line = P.detail_line(P.step_ms(hand_made(), "step_fn", TABLE), 1.25)
    assert json.loads(json.dumps(line)) == line
    ns = 1e-6
    assert line["phase"] == "step_by_role_and_phase"
    assert line["ms"]["mlp"] == pytest.approx(
        {"forward": 200 * ns, "recompute": 250 * ns})
    assert line["ms"]["unresolved"] == pytest.approx(
        {"none": 180 * ns, "update": 50 * ns})
    assert line["inherited_ms"] == {"attn": {"user": pytest.approx(100 * ns)}}
    assert line["provenance_s"] == 1.25
    assert len(line["unresolved"]) == 2


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_with_nothing_to_read_returns_nothing(name, monkeypatch,
                                                       capsys):
    read = harness.layer_reader(name)
    assert read({}, None, {"kind": "train"}) is None
    assert read({}, None, {"kind": "train", "rehearsal": True}) is None
    # a CPU rehearsal's trace holds no device; a serving run is not a step
    assert read({}, {"devices": {}, "host": []}, {"kind": "train"}) is None
    assert read({}, hand_made(), {"kind": "serve_open"}) is None
    # the parent commit's program publishes no provenance
    import paddle_tpu.observability.scopes as program_scopes
    monkeypatch.delattr(program_scopes, "provenance")
    assert read({}, hand_made(), {"kind": "train"}) is None
    # nor one that fails to give it
    monkeypatch.setattr(program_scopes, "provenance",
                        lambda: 1 / 0, raising=False)
    assert read({}, hand_made(), {"kind": "train"}) is None
    assert capsys.readouterr().out == ""


@pytest.fixture(scope="module")
def compiled_step_table():
    """``scopes.provenance()`` of the tiny GPT's step with its blocks
    recomputed, compiled here on the CPU outside the persistent cache (an
    executable cached before the scopes existed carries none), read after
    the step object has gone, as the benchmark's readers read it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       GPTPretrainingCriterion)
    from paddle_tpu.observability import scopes as program_scopes, watchdog
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    kept = dict(watchdog._PROGRAMS)
    watchdog._PROGRAMS.clear()      # what other test files left is not ours
    try:
        paddle.seed(0)
        config = GPTConfig.tiny()
        config.use_recompute = True
        model = GPTForCausalLM(config)
        crit = GPTPretrainingCriterion()
        step = TrainStep(
            model, lambda logits, labels: crit(logits, labels),
            paddle.optimizer.AdamW(parameters=model.parameters(),
                                   learning_rate=1e-4))
        x = jnp.zeros((2, 32), jnp.int32)
        step(x, x)
        del step
        gc.collect()
        tables = program_scopes.provenance()
        yield tables["jit_step_fn"]
    finally:
        watchdog._PROGRAMS.clear()
        watchdog._PROGRAMS.update(kept)
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def test_the_five_values_on_a_compiled_programs_own_table(
        compiled_step_table, monkeypatch, capsys):
    """A trace made of the compiled step's instruction names, 10 ns each,
    read through the table the program publishes."""
    import paddle_tpu.observability.scopes as program_scopes
    table = compiled_step_table
    monkeypatch.setattr(program_scopes, "provenance",
                        lambda: {"jit_step_fn": table})
    monkeypatch.setattr(program_scopes, "index", lambda: {
        "jit_step_fn": program_scopes.own_roles(table)})
    ops = [["%%%s = f32[8]{0} %s(...)" % (name, p.opcode), 10 * i, 10]
           for i, (name, p) in enumerate(table.items())]
    trace = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [["jit_step_fn(7)", 0, 10 * len(ops)]]}},
        "host": []}
    record = {"kind": "train"}
    capsys.readouterr()
    got = {name: harness.layer_reader(name)({}, trace, record)
           for name in READERS}
    each = 10e-6
    count = collections.Counter(p.phase for p in table.values())
    assert count["recompute"] and count["update"] and count[None]
    for phase in ("forward", "backward", "recompute"):
        assert got[phase + "_ms.train"] == pytest.approx(each * count[phase])
    assert got["data_movement_ms.train"] == pytest.approx(
        each * sum(p.moves_only for p in table.values()))
    assert 0 < got["data_movement_ms.train"] < each * len(table)
    assert got["scope_resolved_pct.train"] == pytest.approx(
        100 * sum(p.role is not None for p in table.values()) / len(table))

    # the five phases sum to the busy time scope_coverage_pct.train divides
    # by, and what is resolved holds what is covered
    found = P.train_step_ms(trace, record)
    by_phase = collections.Counter()
    for (_, phase, _), took in found["ms"].items():
        by_phase[phase] += took
    assert set(by_phase) == {"forward", "recompute", "backward", "update",
                             "none"}
    busy = sum(S.train_scope_ms(trace, record).values())
    assert sum(by_phase.values()) == pytest.approx(busy, rel=1e-9)
    assert busy == pytest.approx(each * len(table))
    covered = harness.layer_reader("scope_coverage_pct.train")(
        {}, trace, record)
    assert got["scope_resolved_pct.train"] > covered > 0
    own = sum(took for (_, _, how), took in found["ms"].items()
              if how == "own")
    assert covered == pytest.approx(100 * own / busy)

    # one detail line a trace, one JSON object, whatever the readers' number
    lines = capsys.readouterr().out.splitlines()
    assert len(lines) == 1
    line = json.loads(lines[0])
    assert line["phase"] == "step_by_role_and_phase"
    assert set(line) == {"phase", "ms", "inherited_ms", "moves_only_ms",
                         "unresolved", "provenance_s"}
    assert sum(sum(row.values()) for row in line["ms"].values()) == (
        pytest.approx(busy))
    assert 0 < len(line["unresolved"]) <= P.UNRESOLVED_LISTED
    for group, took, opcode, op_name in line["unresolved"]:
        assert took > 0 and opcode and (op_name is None or len(op_name) <= 120)


def test_a_rehearsals_detail_line_says_so(compiled_step_table, monkeypatch,
                                          capsys):
    import paddle_tpu.observability.scopes as program_scopes
    monkeypatch.setattr(program_scopes, "provenance",
                        lambda: {"jit_step_fn": compiled_step_table})
    trace = {"devices": {"/device:TPU:0": {
        "ops": [["%fusion.1 = f32[8]{0} fusion(...)", 0, 10]],
        "modules": [["jit_step_fn(7)", 0, 10]]}}, "host": []}
    capsys.readouterr()
    assert P.train_step_ms(trace, {"kind": "train", "rehearsal": True})
    (line,) = capsys.readouterr().out.splitlines()
    assert list(json.loads(line))[:2] == ["rehearsal", "phase"]
