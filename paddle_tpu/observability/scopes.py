"""The framework's own names inside its compiled programs.

XLA names what it runs after its own fusions (``fusion.263``,
``divide_subtract_fusion``); a profile read by those names has to guess
which of them is the optimizer.  This module is the one declaration of the
role names the program gives its work, and of how they are read back:

* :data:`VOCABULARY` — a small fixed set of roles.  :func:`scope` opens one
  as a ``jax.named_scope`` where the work is written: ``nn.Layer.__call__``
  from the layer's ``_scope`` class attribute, ``TrainStep`` around the
  update, the serving engine around decode attention, the KV append and the
  sampler.  A scope is metadata (``op_name`` of every operation traced
  inside it): no operation is added, fusion does not read it, and JAX leaves
  it out of the compile-cache key.  Backward operations inherit it
  (``transpose(jvp(attn))``).
* :func:`scope_of` — the role in one ``op_name``, transform wrappers
  stripped, innermost role winning.
* :func:`instruction_scopes` / :func:`index` — ``{instruction name: role}``
  of a compiled program's HLO text, and the same over the programs the
  watched entries compiled, keyed by HLO module name: what maps a device
  trace's events (named by instruction) back to the roles.  Cold path:
  :func:`index` compiles (see ``watchdog.Program``).

Pure stdlib at import, like its neighbours; jax is imported where used.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

__all__ = ["EMBED", "ATTN", "MLP", "NORM", "LM_HEAD", "LOSS", "OPTIMIZER",
           "DECODE_ATTN", "KV_WRITE", "SAMPLE", "PREFILL_ATTN",
           "SSM", "SSM_SCAN", "MOE", "MOE_EXPERTS",
           "LINEAR_ATTN", "LINEAR_ATTN_SCAN",
           "TRAIN", "SERVE", "HYBRID", "LINEAR", "VOCABULARY", "UNSCOPED",
           "scope", "scope_of", "instruction_scopes", "index"]

EMBED = "embed"
ATTN = "attn"
MLP = "mlp"
NORM = "norm"
LM_HEAD = "lm_head"
LOSS = "loss"
OPTIMIZER = "optimizer"
DECODE_ATTN = "decode_attn"
KV_WRITE = "kv_write"
SAMPLE = "sample"
PREFILL_ATTN = "prefill_attn"
SSM = "ssm"
SSM_SCAN = "ssm_scan"
MOE = "moe"
MOE_EXPERTS = "moe_experts"
LINEAR_ATTN = "linear_attn"
LINEAR_ATTN_SCAN = "linear_attn_scan"

#: roles of a training step
TRAIN = (EMBED, ATTN, MLP, NORM, LM_HEAD, LOSS, OPTIMIZER)
#: roles only a serving program has (it has the model's too)
SERVE = (DECODE_ATTN, KV_WRITE, SAMPLE, PREFILL_ATTN)
#: roles of the blocks a hybrid model has beside attention: a state-space
#: mixer (its projections, convolution and gated norm; the scan alone) and
#: a routed expert layer (router, sort, gather, scatter and shared expert;
#: the grouped products alone).  The inner role of each pair wins
HYBRID = (SSM, SSM_SCAN, MOE, MOE_EXPERTS)
#: roles of a linear-attention (Gated DeltaNet) mixer: its projections,
#: convolution, normalisations, gates and gated norm; the delta rule alone
#: (the inner role wins)
LINEAR = (LINEAR_ATTN, LINEAR_ATTN_SCAN)
VOCABULARY = TRAIN + SERVE + HYBRID + LINEAR
_ROLES = frozenset(VOCABULARY)

#: where readers file device time whose instruction carries no role
UNSCOPED = "unscoped"


def scope(name: str):
    """``jax.named_scope(name)`` for a role of :data:`VOCABULARY`; any other
    name is a bug at the call site (a reader would never find it)."""
    if name not in _ROLES:
        raise ValueError("scope %r is not in the vocabulary %r: declare it "
                         "in observability/scopes.py first"
                         % (name, VOCABULARY))
    import jax
    return jax.named_scope(name)


# -- reading the names back ----------------------------------------------------

# ``jit(step_fn)``: the name of a jitted function is not a role even when
# it spells like one
_JIT_NAME = re.compile(r"\bp?jit\([^()]*\)")
# a path element, and whether it opens a wrapper (``jvp(``).  Dots stay in
# the word, so an argument's name (``params['gpt.h.0.attn.weight']``, the
# op_name of a parameter instruction) never spells a role
_ELEMENT = re.compile(r"([A-Za-z_][\w.\-]*)(\()?")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost role in an ``op_name`` such as
    ``jit(step_fn)/transpose(jvp(attn))/dot_general``: transform wrappers
    and jitted functions' names are stripped, and of nested roles the last
    opened wins.  None when the path holds none."""
    found = None
    for word, opens in _ELEMENT.findall(_JIT_NAME.sub("", op_name)):
        if not opens and word in _ROLES:
            found = word
    return found


_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*(?:\([^{]*)?\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FUSION_CALLS = re.compile(r"\bfusion\(.*\bcalls=%?([^\s,)}]+)")


def instruction_scopes(hlo_text: str) -> Tuple[str, Dict[str, Optional[str]]]:
    """``(module name, {instruction name: role or None})`` of one compiled
    program's HLO text, for every instruction that runs as an operation of
    its own: the instructions inside a fusion's computation are left out,
    and the fusion is filed under its own ``op_name`` (XLA gives a fusion
    its root's).  Names are without the leading ``%``."""
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
    out: Dict[str, Optional[str]] = {}
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
            out[m.group(1)] = scope_of(op.group(1)) if op else None
    return module, out


def index() -> Dict[str, Dict[str, Optional[str]]]:
    """``{HLO module name: {instruction name: role or None}}`` over the
    programs the watched entries compiled (``jit_step_fn`` for the train
    step; ``watchdog.Program``).  Each is compiled again, once, to read its
    text: call it after the measurement, never inside it."""
    from . import watchdog
    out: Dict[str, Dict[str, Optional[str]]] = {}
    for program in watchdog.programs():
        module, table = program.instruction_scopes()
        out.setdefault(module, {}).update(table)
    return out
