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
  stripped, innermost role winning; :func:`phase_of` — which of
  :data:`PHASES` the same path says the operation belongs to (``jvp(``,
  ``transpose(``, ``rematted_computation`` and the ``optimizer`` role are
  all JAX writes of a differentiated, checkpointed step).
* :func:`instruction_provenance` / :func:`provenance` — ``{instruction
  name: Provenance}`` of a compiled program's HLO text: role and phase, and
  for an instruction the compiler made (a layout copy has no ``op_name``)
  the role and phase of the work it serves, read off its users or its
  producers inside its own computation (``how``); ``moves_only`` marks a
  pass over memory that computes nothing.  The same over the programs the
  watched entries compiled, keyed by HLO module name: what maps a device
  trace's events (named by instruction) back to the program's words.
* :func:`instruction_scopes` / :func:`index` — the projection of that table
  to ``{instruction name: role}``, the instruction's own ``op_name`` alone
  (what the by-scope readers were built on).  Cold path, all four: the
  program is compiled again to read its text (see ``watchdog.Program``).

Pure stdlib at import, like its neighbours; jax is imported where used.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

__all__ = ["EMBED", "ATTN", "MLP", "NORM", "LM_HEAD", "LOSS", "OPTIMIZER",
           "DECODE_ATTN", "KV_WRITE", "SAMPLE", "PREFILL_ATTN",
           "SSM", "SSM_SCAN", "MOE", "MOE_EXPERTS",
           "LINEAR_ATTN", "LINEAR_ATTN_SCAN",
           "TRAIN", "SERVE", "HYBRID", "LINEAR", "VOCABULARY", "UNSCOPED",
           "PHASES", "FORWARD", "RECOMPUTE", "BACKWARD", "UPDATE",
           "MOVES_ONLY_OPCODES", "HOPS", "Provenance",
           "scope", "scope_of", "phase_of", "instruction_provenance",
           "instruction_scopes", "provenance", "index"]

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

FORWARD = "forward"
RECOMPUTE = "recompute"
BACKWARD = "backward"
UPDATE = "update"
#: the parts of a training step in time: the forward, the forward run a
#: second time inside the backward of a ``jax.checkpoint``, the backward, and
#: the optimizer's update.  A serving program has the first alone
PHASES = (FORWARD, RECOMPUTE, BACKWARD, UPDATE)


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
# what ``jax.checkpoint`` names the forward it runs again in the backward
_REMAT = "rematted_computation"


def _role_and_phase(op_name: str) -> Tuple[Optional[str], Optional[str]]:
    """One walk over an ``op_name`` for :func:`scope_of` and
    :func:`phase_of`."""
    role = None
    remat = transposed = differentiated = False
    for word, opens in _ELEMENT.findall(_JIT_NAME.sub("", op_name)):
        if opens:
            transposed = transposed or word == "transpose"
            differentiated = differentiated or word == "jvp"
        elif word in _ROLES:
            role = word
        elif word == _REMAT:
            remat = True
    if remat:
        phase = RECOMPUTE
    elif transposed:
        phase = BACKWARD
    elif role == OPTIMIZER:
        phase = UPDATE
    elif differentiated or role:
        phase = FORWARD
    else:
        phase = None
    return role, phase


def scope_of(op_name: str) -> Optional[str]:
    """The innermost role in an ``op_name`` such as
    ``jit(step_fn)/transpose(jvp(attn))/dot_general``: transform wrappers
    and jitted functions' names are stripped, and of nested roles the last
    opened wins.  None when the path holds none."""
    return _role_and_phase(op_name)[0]


def phase_of(op_name: str) -> Optional[str]:
    """Which of :data:`PHASES` an ``op_name`` belongs to, by what JAX wrote
    into the path: ``recompute`` where it holds ``rematted_computation``
    (the forward a ``jax.checkpoint`` runs again; also a checkpoint nested
    in a checkpointed block), else ``backward`` under a ``transpose(``
    wrapper (a ``custom_vjp``'s backward rule and the backward of a
    checkpointed block, ``transpose(jvp(jvp()))/checkpoint/attn``,
    included), else ``update`` where the innermost role is ``optimizer``,
    else ``forward`` under a ``jvp(`` wrapper or any role (a serving
    program is all forward).  None where the path says none of these: a
    parameter, a kernel's interpreter, no name at all."""
    return _role_and_phase(op_name)[1]


# -- a compiled program's HLO text ----------------------------------------------

#: opcodes of a pass over memory that computes nothing: an instruction of
#: one of them, an async ``-start``/``-done`` of one, or a fusion whose
#: fused computation holds these alone, is ``moves_only``
MOVES_ONLY_OPCODES = frozenset((
    "copy", "copy-start", "copy-done", "transpose", "reshape", "bitcast",
    "bitcast-convert", "convert", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "broadcast", "reverse",
    "iota", "constant", "parameter", "tuple", "get-tuple-element"))
#: how many role-less instructions (``bitcast``, ``get-tuple-element``,
#: ``tuple``, another copy) an inherited role may cross on its way from the
#: instruction that carries it
HOPS = 4
# instructions that run another computation: what their own ``op_name``
# says stands, and no role is handed through them
_CALLERS = frozenset(("while", "call", "conditional"))
_ASYNC = re.compile(r"-(?:start|done)$")


class Provenance(NamedTuple):
    """What the program can say of one instruction of its compiled text.

    ``how`` is where ``role`` came from: ``"own"`` the instruction's own
    ``op_name`` (a fusion's is its root's), ``"user"`` the one role its
    users agree on, ``"operand"`` the one its producers agree on, None
    where there is none to give.  ``phase`` is the instruction's own where
    its ``op_name`` has one, else found the way the role was."""
    role: Optional[str]
    phase: Optional[str]
    how: Optional[str]
    moves_only: bool
    opcode: str
    op_name: Optional[str]


class _Instruction(NamedTuple):
    name: str
    opcode: str
    operands: Tuple[str, ...]
    called: Tuple[str, ...]
    op_name: Optional[str]


_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*(?:\([^{]*)?\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s*([A-Za-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|false_computation"
    r"|branch_computations)=(?:\{([^}]*)\}|%?([\w.\-]+))")
_OPEN, _CLOSE = "([{", ")]}"


def _closing(text: str, start: int) -> int:
    """Index of the bracket that closes the one at ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        ch = text[i]
        if ch in _OPEN:
            depth += 1
        elif ch in _CLOSE:
            depth -= 1
            if depth == 0:
                return i
    return len(text) - 1


def _operand_names(text: str) -> Tuple[str, ...]:
    """Names in an operand list such as ``%a, f32[8,2]{1,0} %b, 0``: the
    last word of each top-level entry (a constant's literal among them:
    whoever looks a name up finds no instruction)."""
    names, depth, start = [], 0, 0
    for i, ch in enumerate(text + ","):
        if ch in _OPEN:
            depth += 1
        elif ch in _CLOSE:
            depth -= 1
        elif ch == "," and depth == 0:
            words = text[start:i].split()
            if words:
                names.append(words[-1].lstrip("%"))
            start = i + 1
    return tuple(names)


def _parse_instruction(line: str) -> Optional[_Instruction]:
    m = _INSTRUCTION.match(line)
    if not m:
        return None
    name, rest = m.groups()
    # the result's shape first: a tuple's is bracketed and holds spaces
    after_shape = (_closing(rest, 0) + 1 if rest.startswith("(")
                   else rest.find(" "))
    op = _OPCODE.match(rest, max(after_shape, 0))
    if not op:
        return None
    end = _closing(rest, op.end() - 1)
    attributes = rest[end + 1:]
    called = tuple(word.strip().lstrip("%")
                   for several, one in _CALLED.findall(attributes)
                   for word in (several.split(",") if several else [one]))
    found = _OP_NAME.search(attributes)
    return _Instruction(name, op.group(1),
                        _operand_names(rest[op.end():end]), called,
                        found.group(1) if found else None)


def _parse(hlo_text: str) -> Tuple[str, Dict[str, List[_Instruction]]]:
    """``(module name, {computation name: its instructions})``."""
    module, current = "", None
    computations: Dict[str, List[_Instruction]] = {}
    for line in hlo_text.splitlines():
        if not line[:1].isspace():
            if line.startswith("}"):
                current = None
                continue
            if not module:
                m = _MODULE.match(line)
                if m:
                    module = m.group(1)
                    continue
            m = _COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(1), [])
            continue
        if current is not None:
            instruction = _parse_instruction(line)
            if instruction is not None:
                current.append(instruction)
    return module, computations


def _moves_only(instruction: _Instruction,
                computations: Dict[str, List[_Instruction]],
                before: Dict[str, bool]) -> bool:
    """Whether ``instruction`` is a pass that computes nothing; ``before``
    holds the answer for the instructions above it in its computation (an
    ``async-done`` names no computation: it is what its start is)."""
    opcode = instruction.opcode
    if opcode == "fusion" or opcode == "async-start":
        inside = [i for name in instruction.called
                  for i in computations.get(name, ())]
        return bool(inside) and all(
            i.opcode in MOVES_ONLY_OPCODES for i in inside)
    if opcode in ("async-update", "async-done"):
        return any(before.get(o) for o in instruction.operands)
    return (opcode in MOVES_ONLY_OPCODES
            or _ASYNC.sub("", opcode) in MOVES_ONLY_OPCODES)


def _agreed(start: str, edges: Dict[str, Iterable[str]],
            own: Dict[str, Optional[str]], opaque: frozenset
            ) -> Optional[str]:
    """The one value the nearest instructions with a value of their own
    agree on, walking ``edges`` from ``start`` through instructions that
    have none, at most :data:`HOPS` of them; None where there is none or
    they differ."""
    found, seen, frontier = set(), {start}, [start]
    for _ in range(HOPS + 1):
        reached = []
        for name in frontier:
            for other in edges.get(name, ()):
                if other in seen:
                    continue
                seen.add(other)
                value = own.get(other)
                if value is not None:
                    found.add(value)
                elif other not in opaque:
                    reached.append(other)
        frontier = reached
    return found.pop() if len(found) == 1 else None


def _computation_provenance(instructions: List[_Instruction],
                            computations) -> Dict[str, Provenance]:
    names = {i.name for i in instructions}
    operands = {i.name: [o for o in i.operands if o in names]
                for i in instructions}
    users: Dict[str, List[str]] = collections.defaultdict(list)
    for name, sources in operands.items():
        for source in sources:
            users[source].append(name)
    read = {i.name: _role_and_phase(i.op_name) if i.op_name else (None, None)
            for i in instructions}
    roles = {name: role for name, (role, _) in read.items()}
    phases = {name: phase for name, (_, phase) in read.items()}
    opaque = frozenset(i.name for i in instructions if i.opcode in _CALLERS)

    def inherited(name, own):
        for direction, edges in (("user", users), ("operand", operands)):
            value = _agreed(name, edges, own, opaque)
            if value is not None:
                return value, direction
        return None, None

    out, moves = {}, {}
    for i in instructions:
        role, phase = read[i.name]
        how = "own" if role else None
        if i.name not in opaque:
            if role is None:
                role, how = inherited(i.name, roles)
            if phase is None:
                phase, _ = inherited(i.name, phases)
        moves[i.name] = _moves_only(i, computations, moves)
        out[i.name] = Provenance(role, phase, how, moves[i.name], i.opcode,
                                 i.op_name)
    return out


def instruction_provenance(hlo_text: str
                           ) -> Tuple[str, Dict[str, Provenance]]:
    """``(module name, {instruction name: Provenance})`` of one compiled
    program's HLO text, for every instruction that runs as an operation of
    its own: the instructions inside a fusion's computation are left out.
    Names are without the leading ``%``.

    An instruction whose own ``op_name`` holds no role (the compiler made
    it: a layout copy, a ``bitcast``, a ``tuple``; or JAX named it under no
    role: a residual add) takes the role its users agree on, else the one
    its producers agree on, inside its own computation and across at most
    :data:`HOPS` role-less instructions; a ``while``, ``call`` or
    ``conditional`` keeps what its own name says and hands nothing
    through.  A missing phase is found the same way."""
    module, computations = _parse(hlo_text)
    fused = {name for instructions in computations.values()
             for i in instructions if i.opcode == "fusion"
             for name in i.called}
    out: Dict[str, Provenance] = {}
    for name, instructions in computations.items():
        if name not in fused:
            out.update(_computation_provenance(instructions, computations))
    return module, out


def own_roles(table: Dict[str, Provenance]) -> Dict[str, Optional[str]]:
    """``{instruction name: role or None}``: the roles the instructions'
    own ``op_name``s carry, without what was inherited."""
    return {name: p.role if p.how == "own" else None
            for name, p in table.items()}


def instruction_scopes(hlo_text: str) -> Tuple[str, Dict[str, Optional[str]]]:
    """``(module name, {instruction name: role or None})``:
    :func:`instruction_provenance` without what it inherited, a fusion
    filed under its own ``op_name`` (XLA gives a fusion its root's)."""
    module, table = instruction_provenance(hlo_text)
    return module, own_roles(table)


def _over_programs(table_of) -> Dict[str, dict]:
    """``{HLO module name: table}`` over the programs the watched entries
    compiled (``watchdog.Program``), ``table_of`` giving each program's
    ``(module name, table)``."""
    from . import watchdog
    out: Dict[str, dict] = {}
    for program in watchdog.programs():
        module, table = table_of(program)
        out.setdefault(module, {}).update(table)
    return out


def provenance() -> Dict[str, Dict[str, Provenance]]:
    """``{HLO module name: {instruction name: Provenance}}`` over the
    programs the watched entries compiled (``jit_step_fn`` for the train
    step).  Each is compiled again, once, to read its text: call it after
    the measurement, never inside it."""
    return _over_programs(lambda program: program.provenance())


def index() -> Dict[str, Dict[str, Optional[str]]]:
    """``{HLO module name: {instruction name: role or None}}``:
    :func:`provenance` projected as :func:`instruction_scopes` projects
    (one compile and one parse serve both)."""
    return _over_programs(lambda program: program.instruction_scopes())
