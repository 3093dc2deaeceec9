"""The program's own names inside its compiled programs
(``observability/scopes.py``): every role reaches the lowered text of the
program that does its work, forward and backward; a layer that names no
role adds none; and the index from executed instruction to role is read
from the compiled program, once.

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


def test_remembered_programs_are_bounded():
    x = jnp.ones((2, 4), jnp.float32)
    for _ in range(watchdog._PROGRAMS_KEPT + 3):
        entry = watchdog.watch("test.bounded", jax.jit(lambda a: a + 1))
        entry(x)
    kept = [p for p in watchdog.programs() if p.entry_name == "test.bounded"]
    assert len(kept) == watchdog._PROGRAMS_KEPT
