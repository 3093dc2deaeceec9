"""The program's own names inside its compiled programs
(``observability/scopes.py``): every role reaches the lowered text of the
program that does its work, forward and backward; a layer that names no
role adds none; the index from executed instruction to role is read
from the compiled program, once; and the same reading says which phase of
the step an instruction belongs to and gives what the compiler made the
role of the work it serves (``instruction_provenance``).

CPU, tiny GPT.  What a role's device time is on the chip is the
benchmark's to say (``benchmarks/lib/scopes.py``)."""
import gc
import re

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.dtype import x64_scope
from paddle_tpu.jit import TrainStep
from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
from paddle_tpu.observability import scopes, watchdog

_LOC = re.compile(r'loc\("([^"]+)"')


def _tiny_model():
    paddle.seed(0)
    return GPTForCausalLM(GPTConfig.tiny())


def _train_step(model):
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-4, weight_decay=0.01)
    return TrainStep(model, lambda logits, labels: crit(logits, labels),
                     opt)


def _op_names(lowered):
    """Every ``op_name`` of a lowered program (its MLIR locations)."""
    return set(_LOC.findall(lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def train_op_names():
    step = _train_step(_tiny_model())
    x = jnp.zeros((2, 32), jnp.int32)
    names = _op_names(step._step.lower(*step.trace_args((x, x))))
    del step
    gc.collect()
    return names


@pytest.mark.parametrize("role", [r for r in scopes.TRAIN
                                  if r != scopes.OPTIMIZER])
def test_train_step_names_the_role_forward_and_backward(train_op_names,
                                                        role):
    mine = [n for n in train_op_names if scopes.scope_of(n) == role]
    assert any("transpose(" not in n for n in mine), (role, "no forward op")
    assert any(n.split("/")[1].startswith("transpose(jvp(") for n in mine), (
        role, "no backward op inherits the role")


def test_train_step_names_the_update(train_op_names):
    mine = [n for n in train_op_names
            if scopes.scope_of(n) == scopes.OPTIMIZER]
    assert mine and all("jvp" not in n for n in mine)
    # and nothing of the model's forward or backward is filed there
    assert not [n for n in mine if "dot_general" in n]


@pytest.fixture(scope="module")
def serving_op_names():
    from paddle_tpu.serving.engine import DecodeEngine
    model = _tiny_model()
    model.eval()
    out = {}
    paged = DecodeEngine(model, num_slots=2, max_len=64, page_size=16)
    slotted = DecodeEngine(model, num_slots=2, max_len=64, paged=False)
    with x64_scope(False):      # the engine's production trace scope
        for key, eng, fn, args in (
                ("paged_decode", paged, paged._decode_fn,
                 paged.decode_trace_args()),
                ("prefill_chunk", paged, paged._prefill_chunk_fn,
                 paged.prefill_chunk_trace_args()),
                ("slotted_decode", slotted, slotted._decode_fn,
                 slotted.decode_trace_args()),
                ("slotted_prefill", slotted, slotted._prefill_fn,
                 slotted.prefill_trace_args())):
            out[key] = {scopes.scope_of(n)
                        for n in _op_names(jax.jit(fn).lower(*args))}
    del paged, slotted
    gc.collect()
    return out


@pytest.mark.parametrize("program,attention", [
    ("paged_decode", scopes.DECODE_ATTN),
    ("slotted_decode", scopes.DECODE_ATTN),
    ("prefill_chunk", scopes.PREFILL_ATTN),
    ("slotted_prefill", scopes.PREFILL_ATTN),
])
def test_serving_programs_name_their_work(serving_op_names, program,
                                          attention):
    found = serving_op_names[program]
    other = ({scopes.DECODE_ATTN, scopes.PREFILL_ATTN} - {attention}).pop()
    assert {attention, scopes.KV_WRITE, scopes.SAMPLE} <= found
    assert other not in found
    # the model's own blocks are named in a serving program too
    assert {scopes.EMBED, scopes.ATTN, scopes.MLP, scopes.NORM,
            scopes.LM_HEAD} <= found
    assert scopes.OPTIMIZER not in found and scopes.LOSS not in found


def test_a_layer_without_the_attribute_adds_no_scope():
    class Plain(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 4)

        def forward(self, x):
            return self.fc(x) * 2.0

    assert nn.Layer._scope is None and nn.Linear._scope is None
    assert nn.LayerNorm._scope == scopes.NORM
    assert nn.Embedding._scope == scopes.EMBED
    net = Plain()
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(),
                     paddle.optimizer.SGD(parameters=net.parameters(),
                                          learning_rate=0.1))
    x = jnp.ones((2, 4), jnp.float32)
    names = _op_names(step._step.lower(*step.trace_args((x, x))))
    assert {scopes.scope_of(n) for n in names} == {None, scopes.OPTIMIZER}


def test_scope_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="not in the vocabulary"):
        scopes.scope("attention")
    assert len(set(scopes.VOCABULARY)) == len(scopes.VOCABULARY) == 17


@pytest.mark.parametrize("op_name,role", [
    ("jit(step_fn)/jvp(attn)/dot_general", "attn"),
    ("jit(step_fn)/transpose(jvp(attn))/dot_general", "attn"),
    ("jit(step_fn)/transpose(jvp(attn/norm))/mul", "norm"),
    ("jit(decode_fn)/attn/decode_attn/reduce_max", "decode_attn"),
    ("jit(step_fn)/optimizer/sqrt", "optimizer"),
    ("jit(step_fn)/jvp(attn)/jit(_where)/select_n", "attn"),
    # a jitted function, a parameter's name and a transform are not roles
    ("jit(loss)/mul", None),
    ("params['gpt.h.0.attn.qkv_proj.weight']", None),
    ("opt_state['slots']['gpt.h.1.mlp.fc1.bias']['moment1']", None),
    ("jit(step_fn)/jvp()/add", None),
    ("", None),
])
def test_scope_of(op_name, role):
    assert scopes.scope_of(op_name) == role


HLO = '''HloModule jit_step_fn, entry_computation_layout={()->f32[]}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %inner.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step_fn)/jvp(mlp)/mul"}
}

%body (arg: f32[8]) -> f32[8] {
  %arg = f32[8]{0} parameter(0)
  ROOT %in_loop.3 = f32[8]{0} add(%arg, %arg), metadata={op_name="jit(step_fn)/optimizer/add"}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="params['gpt.h.0.attn.w']"}
  %fusion.7 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/transpose(jvp(attn))/mul"}
  %copy.2 = f32[8]{0} copy(%fusion.7)
  ROOT %flash_fwd.4 = f32[8]{0} custom-call(%copy.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jvp(attn)/flash_fwd/pallas_call"}
}
'''


def test_instruction_scopes_reads_hlo_text():
    module, table = scopes.instruction_scopes(HLO)
    assert module == "jit_step_fn"
    # a fusion is filed under its own op_name; what it fused is left out
    assert table == {"x": None, "fusion.7": "attn", "copy.2": None,
                     "flash_fwd.4": "attn", "arg": None,
                     "in_loop.3": "optimizer"}


@pytest.mark.parametrize("op_name,phase", [
    # the six shapes a checkpointed, differentiated step gives its work
    ("jit(step)/jvp(attn)/dot_general", "forward"),
    ("jit(step)/transpose(jvp(attn))/dot_general", "backward"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/attn/"
     "dot_general", "recompute"),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/attn/mul", "backward"),
    ("jit(step)/optimizer/mul", "update"),
    ("jit(step)/jvp()/add", "forward"),
    # a custom_vjp's rules: the forward under jvp, the backward rule under
    # transpose, both through the kernels' jitted builders
    ("jit(step_fn)/jvp(attn)/jit(_flash_fwd_inner)/flash_fwd/pallas_call",
     "forward"),
    ("jit(step_fn)/transpose(jvp(attn))/jit(_bwd_resident)/flash_bwd/"
     "pallas_call", "backward"),
    ("jit(step_fn)/transpose(jvp(moe))/moe_experts/jit(tgmm)/while/body/"
     "dot_general", "backward"),
    # optimize_remat=True: a recomputed block's first forward, and the
    # kernel the recomputation runs
    ("jit(step_fn)/jvp(ssm)/ssm_scan/jit(_forward)/ssd_scan_fwd/pallas_call",
     "forward"),
    ("jit(step_fn)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "linear_attn/linear_attn_scan/jit(_forward)/delta_rule_fwd/pallas_call",
     "recompute"),
    ("jit(step_fn)/transpose(jvp(jvp()))/checkpoint/linear_attn/"
     "linear_attn_scan/jit(_backward)/delta_rule_bwd/pallas_call",
     "backward"),
    # a checkpoint nested in a checkpointed block recomputes in the backward
    ("jit(step_fn)/transpose(jvp(jvp()))/checkpoint/ssm/ssm_scan/checkpoint/"
     "rematted_computation/mul", "recompute"),
    ("jit(step_fn)/jvp(ssm)/ssm_scan/checkpoint/mul", "forward"),
    # a cond's branch, a while's body, a pjit-nested name
    ("jit(step_fn)/jvp(moe)/moe_experts/jit(gmm)/while/body/cond/"
     "branch_1_fun/dot_general", "forward"),
    ("jit(step_fn)/transpose(jvp(jvp()))/checkpoint/moe/moe_experts/"
     "jit(tgmm)/while/body/cond/branch_1_fun/mul", "backward"),
    ("jit(step_fn)/jvp(attn)/pjit(_where)/select_n", "forward"),
    ("pjit(step_fn)/transpose(jvp(attn))/pjit(_where)/select_n", "backward"),
    # a primitive called transpose is no wrapper; XLA's merged names
    ("jit(step_fn)/jvp(attn)/transpose", "forward"),
    ("jit(step_fn)/jvp(ssm)/ssm_scan/reshape;jit(step_fn)/jvp(ssm)",
     "forward"),
    # a serving program is all forward
    ("jit(decode_fn)/attn/decode_attn/reduce_max", "forward"),
    # no wrapper and no role: a parameter, the interpreter's loop, nothing
    ("params['gpt.h.0.attn.qkv_proj.weight']", None),
    ("flash_fwd/while/body/dot_general", None),
    ("jit(transpose)/mul", None),
    ("", None),
])
def test_phase_of(op_name, phase):
    assert scopes.phase_of(op_name) == phase
    assert phase is None or phase in scopes.PHASES


def test_the_phases_are_declared():
    assert scopes.PHASES == ("forward", "recompute", "backward", "update")
    assert not set(scopes.PHASES) & set(scopes.VOCABULARY)


# what the compiler makes around named work: a layout copy and a bitcast in
# front of a kernel, a copy two layers disagree on, a nameless product
# inside a loop's body, fusions that move and that compute
PROVENANCE_HLO = '''HloModule jit_step_fn, entry_computation_layout={()->f32[]}

%fused_moves (p: f32[8,4]) -> f32[4,8] {
  %p.1 = f32[8,4]{1,0} parameter(0)
  %copy.9 = f32[8,4]{0,1} copy(%p.1)
  ROOT %transpose.9 = f32[4,8]{1,0} transpose(%copy.9), dimensions={1,0}
}

%fused_adds (p: f32[8]) -> f32[8] {
  %p.2 = f32[8]{0} parameter(0)
  %convert.9 = f32[8]{0} convert(%p.2)
  ROOT %add.9 = f32[8]{0} add(%convert.9, %convert.9)
}

%async_slice (p: f32[8]) -> f32[4] {
  %p.3 = f32[8]{0} parameter(0)
  ROOT %slice.9 = f32[4]{0:S(1)} slice(%p.3), slice={[0:4]}, backend_config={"flag_configs":[]}
}

%body (arg: (f32[8], f32[8])) -> (f32[8], f32[8]) {
  %arg = (f32[8]{0}, f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=0
  %dot.1 = f32[8]{0} dot(%gte.1, %gte.1)
  ROOT %tuple.1 = (f32[8]{0}, f32[8]{0}) tuple(%dot.1, %gte.1)
}

%condition (arg: (f32[8], f32[8])) -> pred[] {
  %arg.2 = (f32[8]{0}, f32[8]{0}) parameter(0)
  ROOT %constant.2 = pred[] constant(true)
}

ENTRY %main.9 (x: f32[8], y: f32[8,4]) -> (f32[8], f32[8]) {
  %x = f32[8]{0:T(8,128)} parameter(0), metadata={op_name="params['gpt.h.0.attn.w']"}
  %y = f32[8,4]{1,0} parameter(1), metadata={op_name="batch[0]"}
  %norm.1 = f32[8]{0} multiply(%x, %x), metadata={op_name="jit(step_fn)/jvp(norm)/mul"}
  %copy.1 = f32[8]{0} copy(%norm.1)
  %bitcast.1 = f32[8]{0} bitcast(%copy.1)
  %flash_fwd.4 = (f32[8]{0}, f32[8]{0}) custom-call(%bitcast.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/jvp(attn)/flash_fwd/pallas_call"}
  %gte.4 = f32[8]{0} get-tuple-element(%flash_fwd.4), index=0
  %copy.2 = f32[8]{0} copy(%gte.4)
  %mlp.1 = f32[8]{0} add(%copy.2, %copy.2), metadata={op_name="jit(step_fn)/jvp(mlp)/add"}
  %moe.1 = f32[8]{0} add(%copy.2, %copy.2), metadata={op_name="jit(step_fn)/transpose(jvp(jvp()))/checkpoint/rematted_computation/moe/add"}
  %residual.1 = f32[8]{0} add(%mlp.1, %moe.1), metadata={op_name="jit(step_fn)/jvp()/add"}
  %moves.1 = f32[4,8]{1,0} fusion(%y), kind=kLoop, calls=%fused_moves
  %adds.1 = f32[8]{0} fusion(%residual.1), kind=kLoop, calls=%fused_adds
  %tuple.2 = (f32[8]{0}, f32[8]{0}) tuple(%adds.1, %mlp.1)
  %while.1 = (f32[8]{0}, f32[8]{0}) while(%tuple.2), condition=%condition, body=%body
  %gte.5 = f32[8]{0} get-tuple-element(%while.1), index=0
  %update.1 = f32[8]{0} subtract(%x, %gte.5), metadata={op_name="jit(step_fn)/optimizer/sub"}
  %slice-start.1 = ((f32[8]{0}), f32[4]{0:S(1)}, s32[]{:S(2)}) async-start(%update.1), calls=%async_slice
  %slice-done.1 = f32[4]{0:S(1)} async-done(%slice-start.1)
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%update.1)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  ROOT %tuple.3 = (f32[8]{0}, f32[8]{0}) tuple(%copy-done.1, /*index=1*/%gte.5)
}
'''


def test_instruction_provenance_reads_hlo_text():
    module, table = scopes.instruction_provenance(PROVENANCE_HLO)
    assert module == "jit_step_fn"
    got = {k: (p.role, p.phase, p.how) for k, p in table.items()}
    # the instruction's own name, as before
    assert got["norm.1"] == ("norm", "forward", "own")
    assert got["flash_fwd.4"] == ("attn", "forward", "own")
    assert got["update.1"] == ("optimizer", "update", "own")
    # copy -> bitcast -> kernel: the kernel's role and phase, by its user
    assert got["copy.1"] == got["bitcast.1"] == ("attn", "forward", "user")
    # users that disagree (mlp and moe, forward and recompute): the
    # producer's, through a get-tuple-element
    assert got["copy.2"] == ("attn", "forward", "operand")
    # named under no role: its own phase stands; its producers disagree and
    # its one user has no role either
    assert got["residual.1"] == (None, "forward", None)
    # behind it a fusion with no name: a role from nobody, the phase from
    # its producer; the loop hands nothing through and keeps what it says
    assert got["adds.1"] == (None, "forward", None)
    assert got["while.1"] == (None, None, None)
    assert got["gte.5"] == ("optimizer", "update", "user")
    # the async pair behind the update: by operand
    assert got["copy-start.1"] == got["copy-done.1"] == (
        "optimizer", "update", "operand")
    # inside the loop's body nothing is guessed from outside it
    assert got["dot.1"] == got["gte.1"] == got["tuple.1"] == (
        None, None, None)
    # a fusion's computation is left out, as instruction_scopes leaves it
    assert not {"copy.9", "transpose.9", "add.9", "convert.9"} & set(table)
    assert table["moves.1"].opcode == "fusion"
    assert table["flash_fwd.4"].op_name.endswith("flash_fwd/pallas_call")
    assert table["copy.1"].op_name is None


def test_moves_only_is_a_pass_that_computes_nothing():
    _, table = scopes.instruction_provenance(PROVENANCE_HLO)
    moving = {k for k, p in table.items() if p.moves_only}
    # a fusion of copy + transpose moves; one with an add computes
    assert "moves.1" in moving and "adds.1" not in moving
    assert {"copy.1", "bitcast.1", "copy-start.1", "copy-done.1", "gte.4",
            "tuple.3", "x"} <= moving
    # an async pair is what the computation its start names is
    assert {"slice-start.1", "slice-done.1"} <= moving
    assert table["slice-done.1"][:3] == ("optimizer", "update", "operand")
    assert not {"norm.1", "flash_fwd.4", "while.1", "dot.1",
                "update.1"} & moving
    assert {"copy", "convert", "transpose", "reshape", "bitcast",
            "dynamic-update-slice"} <= scopes.MOVES_ONLY_OPCODES
    assert not {"add", "fusion", "dot", "custom-call", "while",
                "reduce"} & scopes.MOVES_ONLY_OPCODES


def test_a_role_crosses_a_bounded_number_of_nameless_instructions():
    last = scopes.HOPS + 2
    chain = "".join(
        "  %%bitcast.%d = f32[8]{0} bitcast(%%bitcast.%d)\n" % (i + 1, i)
        for i in range(last))
    text = ("HloModule m\n\nENTRY %main (x: f32[8]) -> f32[8] {\n"
            "  %bitcast.0 = f32[8]{0} parameter(0)\n" + chain +
            "  ROOT %out = f32[8]{0} add(%bitcast." + str(last) +
            ", %bitcast." + str(last) +
            "), metadata={op_name=\"jit(f)/jvp(mlp)/add\"}\n}\n")
    _, table = scopes.instruction_provenance(text)
    assert table["bitcast.%d" % last].how == "user"
    assert table["bitcast.%d" % (last - scopes.HOPS)].role == "mlp"
    assert table["bitcast.%d" % (last - scopes.HOPS - 1)].role is None


def _old_instruction_scopes(hlo_text):
    """``scopes.instruction_scopes`` as it stood before it became a
    projection of ``instruction_provenance`` (PR 25's parser, verbatim)."""
    _MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
    _COMPUTATION = re.compile(
        r"^(?:ENTRY\s+)?%?([^\s(]+)\s*(?:\([^{]*)?\{\s*$")
    _INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s")
    _OP_NAME = re.compile(r'op_name="([^"]*)"')
    _FUSION_CALLS = re.compile(r"\bfusion\(.*\bcalls=%?([^\s,)}]+)")
    lines = hlo_text.splitlines()
    module = ""
    fused = set()
    for line in lines:
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
        m = _FUSION_CALLS.search(line)
        if m:
            fused.add(m.group(1))
    out = {}
    skipping = False
    for line in lines:
        if line.startswith("}"):
            skipping = False
            continue
        if not line[:1].isspace():
            m = _COMPUTATION.match(line)
            if m:
                skipping = m.group(1) in fused
            continue
        if skipping:
            continue
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = scopes.scope_of(op.group(1)) if op else None
    return module, out


@pytest.mark.parametrize("which", ["roles", "provenance"])
def test_instruction_scopes_is_the_projection_of_the_provenance(which):
    text = {"roles": HLO, "provenance": PROVENANCE_HLO}[which]
    assert scopes.instruction_scopes(text) == _old_instruction_scopes(text)
    module, table = scopes.instruction_provenance(text)
    assert scopes.instruction_scopes(text) == (
        module, {k: p.role if p.how == "own" else None
                 for k, p in table.items()})


@pytest.fixture
def no_persistent_cache():
    """Scopes are metadata, and metadata is not in the compile cache's key:
    a cache directory filled before the scopes existed hands back an
    executable without them.  The index is tested on a fresh compile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.usefixtures("no_persistent_cache")
def test_the_index_comes_from_the_compiled_program_once(monkeypatch):
    """``instruction_scopes()`` maps the instructions of the program the
    entry runs to roles; a second call compiles nothing; and the program
    outlives the step object, for a reader that comes after it."""
    # ``index()`` reads every program this process remembers: what other
    # test files left would be compiled inside the listening window below
    monkeypatch.setattr(watchdog, "_PROGRAMS", {})
    step = _train_step(_tiny_model())
    x = jnp.zeros((2, 32), jnp.int32)
    step(x, x)
    tables = step._step.instruction_scopes()
    assert list(tables) == ["jit_step_fn"]
    table = tables["jit_step_fn"]
    found = set(table.values())
    assert set(scopes.TRAIN) <= found and None in found
    # the names are the compiled program's own instructions
    text = step._step.lower(*step.trace_args((x, x))).compile().as_text()
    named = [k for k, v in table.items() if v is not None]
    assert named and all(re.search(r"%?" + re.escape(k) + r" = ", text)
                         for k in named[:50])

    seen = []

    def listener(event, secs, **kw):
        seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        assert step._step.instruction_scopes() == tables
        assert scopes.index() == tables
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert not [e for e in seen if "/jax/core/compile/" in e], seen
    del step
    gc.collect()
    assert not [e for e in watchdog.live_entries()
                if e.entry_name == "jit.train_step"]
    assert scopes.index() == tables


class _CountingTraced:
    """A ``Program``'s traced step with its ``lower()`` calls counted."""

    def __init__(self, traced):
        self.traced, self.lowered = traced, 0

    def lower(self):
        self.lowered += 1
        return self.traced.lower()


@pytest.mark.usefixtures("no_persistent_cache")
def test_a_checkpointed_step_lands_in_every_phase(monkeypatch):
    """The tiny GPT's really compiled step with its blocks under
    ``jax.checkpoint``: some instruction is filed under each phase, the
    recomputed forward under the blocks' own roles; ``instruction_scopes``
    of the compiled text is what the old parser gave; and one compile and
    one parse serve both tables of the ``Program``."""
    monkeypatch.setattr(watchdog, "_PROGRAMS", {})
    paddle.seed(0)
    config = GPTConfig.tiny()
    config.use_recompute = True
    step = _train_step(GPTForCausalLM(config))
    x = jnp.zeros((2, 32), jnp.int32)
    step(x, x)
    (program,) = watchdog.programs()
    counted = program._traced = _CountingTraced(program._traced)
    parses = []
    parse = scopes.instruction_provenance
    monkeypatch.setattr(scopes, "instruction_provenance",
                        lambda text: parses.append(1) or parse(text))
    assert program.read_seconds is None
    module, table = program.provenance()
    assert module == "jit_step_fn"
    assert program.instruction_scopes() == (module, scopes.own_roles(table))
    assert program.provenance()[1] is table
    assert scopes.provenance() == {module: table}
    assert scopes.index() == {module: scopes.own_roles(table)}
    assert step._step.instruction_scopes() == scopes.index()
    assert counted.lowered == 1 and parses == [1]
    assert program.read_seconds > 0

    by_phase = {}
    for p in table.values():
        by_phase.setdefault(p.phase, set()).add(p.role)
    assert set(scopes.PHASES) <= set(by_phase)
    # the blocks are recomputed; embedding, head and loss are not
    assert {scopes.ATTN, scopes.MLP, scopes.NORM} <= by_phase["recompute"]
    assert not {scopes.EMBED, scopes.LM_HEAD, scopes.LOSS,
                scopes.OPTIMIZER} & by_phase["recompute"]
    assert {scopes.ATTN, scopes.MLP, scopes.LOSS} <= by_phase["backward"]
    assert {scopes.EMBED, scopes.LOSS} <= by_phase["forward"]
    assert by_phase["update"] == {scopes.OPTIMIZER}
    assert {"own", "user", None} <= {p.how for p in table.values()}
    assert all((p.role is None) == (p.how is None) for p in table.values())
    text = counted.traced.lower().compile().as_text()
    assert scopes.instruction_scopes(text) == _old_instruction_scopes(text)
    assert scopes.instruction_scopes(text) == program.instruction_scopes()
    del step
    gc.collect()


def test_the_programs_report_counts_instructions_by_role_phase_and_how():
    """What ``python -m paddle_tpu.observability programs`` prints beside
    a program's row: its instructions by role, phase and where the role
    came from, and how many have no owner."""
    from paddle_tpu.observability import costs

    def step_fn(w, x):
        def loss(w):
            with scopes.scope(scopes.MLP):
                h = jnp.sin(x @ w)
            with scopes.scope(scopes.LOSS):
                return (h ** 2).mean()
        value, grad = jax.value_and_grad(loss)(w)
        with scopes.scope(scopes.OPTIMIZER):
            return value, w - 0.1 * grad

    compiled = jax.jit(step_fn).lower(
        jnp.eye(16, dtype=jnp.float32),
        jnp.ones((4, 16), jnp.float32)).compile()
    report = costs.report_from_compiled("tiny_step", compiled)
    _, table = scopes.instruction_provenance(compiled.as_text())
    assert sum(report.scope_ops.values()) == len(table) == sum(
        report.provenance_ops.values())
    assert report.scope_ops == costs.instruction_counts(compiled)["scope_ops"]
    keys = set(report.provenance_ops)
    assert {"mlp/forward/own", "mlp/backward/own",
            "optimizer/update/own", costs.UNRESOLVED} <= keys
    assert any(k.endswith(("/user", "/operand")) for k in keys)
    inherited = sum(n for k, n in report.provenance_ops.items()
                    if k.endswith(("/user", "/operand")))
    assert report.scope_ops[scopes.UNSCOPED] == (
        inherited + report.provenance_ops[costs.UNRESOLVED])
    lines = costs.format_table([report]).splitlines()
    assert lines[1].startswith("tiny_step") and "mlp:" in lines[1]
    rows = {line.split()[0]: line for line in lines[2:-1]}
    assert set(rows) == {k.split("/")[0] for k in keys}
    assert "forward" in rows["mlp"] and "backward" in rows["mlp"]
    assert rows["optimizer"].split()[1] == "update"
    assert rows[costs.UNRESOLVED].split() == [
        costs.UNRESOLVED, str(report.provenance_ops[costs.UNRESOLVED])]
    assert report.as_dict()["provenance_ops"] == report.provenance_ops


def test_remembered_programs_are_bounded():
    x = jnp.ones((2, 4), jnp.float32)
    for _ in range(watchdog._PROGRAMS_KEPT + 3):
        entry = watchdog.watch("test.bounded", jax.jit(lambda a: a + 1))
        entry(x)
    kept = [p for p in watchdog.programs() if p.entry_name == "test.bounded"]
    assert len(kept) == watchdog._PROGRAMS_KEPT
