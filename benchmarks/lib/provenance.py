"""Device time of a training step by the program's role AND phase.

``lib/scopes.py`` files a step's device time under the role each executed
instruction carries in its own ``op_name``; what the compiler made (layout
copies, casts, tuples) carries none and lands in ``unscoped``.  The program
can say more (``paddle_tpu.observability.scopes.provenance()``): for every
instruction of the compiled step its role, its phase (``forward``,
``recompute``, ``backward``, ``update``), ``how`` the role was found
(``own`` name, the ``user``s' or the ``operand``s' where the instruction has
none) and whether it only moves data (``moves_only``).  This module joins
that table with the reduced trace, as ``lib/scopes.py`` joins the roles:

* :func:`step_ms` — self time (``lib/scopes.py::self_times``) of one
  execution of ``jit_<program>``, summed by ``(role or "unresolved", phase
  or "none", how)``, the ``moves_only`` part by role, and the costliest
  instruction groups no role was found for.  The sums over all keys equal
  the device's busy time inside the program's executions, which is what
  ``scope_coverage_pct.train`` divides by.
* :func:`train_step_ms` — the same for this run's training step, computed
  once for the five readers that share it; its first computation in a
  traced run prints one detail line, ``{"phase":
  "step_by_role_and_phase", ...}``, before the result's line.

Everything a reader calls here returns None where there is nothing to
read: no trace, a program that publishes no provenance (the parent commit
of the PR that added it), a program name the trace does not hold.
"""
from __future__ import annotations

import json
from typing import Callable, Dict, Optional

from benchmarks.lib import trace as trace_mod
from benchmarks.lib.scopes import instruction_name, self_times

UNRESOLVED = "unresolved"
NO_PHASE = "none"
#: instruction groups without a role that the detail line names
UNRESOLVED_LISTED = 20
_OP_NAME_HEAD = 120


def step_ms(trace: dict, program: str, tables: Dict[str, dict]
            ) -> Optional[dict]:
    """``{"ms": {(role, phase, how): ms}, "moves_only_ms": {role: ms},
    "unresolved": [[group, ms, opcode, op_name head or None], ...]}`` of
    one execution of ``jit_<program>`` (the mean over the executions the
    trace holds) on the first device that ran it.  An event whose
    instruction the table does not know is ``("unresolved", "none",
    None)``.  None when the trace does not hold the program or ``tables``
    does not know it."""
    table = tables.get("jit_" + program)
    if table is None:
        return None
    for dev in trace["devices"].values():
        runs = trace_mod._runs_of(dev, program)
        if not runs:
            continue
        spans = [(s, s + d) for _, s, d in runs]
        ops = [e for e in dev["ops"]
               if any(a <= e[1] < b for a, b in spans)]
        ms: Dict[tuple, float] = {}
        moves: Dict[str, float] = {}
        nameless: Dict[str, list] = {}
        per_run = 1e-6 / len(runs)
        for event, own in zip(ops, self_times(ops)):
            p = table.get(instruction_name(event[0]))
            role = (p and p.role) or UNRESOLVED
            key = (role, (p and p.phase) or NO_PHASE, p and p.how)
            ms[key] = ms.get(key, 0.0) + own * per_run
            if p and p.moves_only:
                moves[role] = moves.get(role, 0.0) + own * per_run
            if role == UNRESOLVED:
                group = nameless.setdefault(
                    trace_mod.op_group(event[0]),
                    [0.0, p and p.opcode, p and p.op_name])
                group[0] += own * per_run
        ranked = sorted(nameless.items(), key=lambda kv: -kv[1][0])
        return {"ms": ms, "moves_only_ms": moves,
                "unresolved": [
                    [group, took, opcode,
                     op_name[:_OP_NAME_HEAD] if op_name else None]
                    for group, (took, opcode, op_name)
                    in ranked[:UNRESOLVED_LISTED]]}
    return None


def _nested(ms: Dict[tuple, float], outer: int, inner: int,
            keep: Callable[[tuple], bool] = lambda key: True) -> dict:
    """``{key[outer]: {key[inner]: ms}}`` over the keys ``keep`` accepts."""
    out: Dict[str, Dict[str, float]] = {}
    for key, took in ms.items():
        if keep(key):
            row = out.setdefault(key[outer], {})
            row[key[inner]] = row.get(key[inner], 0.0) + took
    return out


def detail_line(found: dict, seconds: Optional[float]) -> dict:
    """The detail line's fields: the role x phase table in ms a step, what
    was inherited by role and direction, the ``moves_only`` part by role,
    the costliest instruction groups without a role, and the seconds the
    program's side took to read its compiled text."""
    return {"phase": "step_by_role_and_phase",
            "ms": _nested(found["ms"], 0, 1),
            "inherited_ms": _nested(
                found["ms"], 0, 2, lambda key: key[2] in ("user", "operand")),
            "moves_only_ms": found["moves_only_ms"],
            "unresolved": found["unresolved"],
            "provenance_s": seconds}


def _program_tables():
    """``(scopes.provenance(), seconds its compile look-ups and parses
    took)`` of the program under test; the seconds are the program's own
    note (an earlier reader's ``scopes.index()`` has paid them by now)."""
    from paddle_tpu.observability import scopes, watchdog
    tables = scopes.provenance()
    return tables, sum(p.read_seconds or 0.0 for p in watchdog.programs())


_memo: dict = {}


def train_step_ms(trace, run) -> Optional[dict]:
    """:func:`step_ms` of this run's training step with the table the
    program publishes, computed once a trace.  None where the program has
    no provenance to give, or anything else goes wrong: a reader must never
    end a traced run."""
    if not trace or not trace.get("devices") or run.get("kind") != "train":
        return None
    if _memo.get("trace") is not trace:
        try:
            tables, seconds = _program_tables()
            found = step_ms(trace, "step_fn", tables)
        except Exception:
            found, seconds = None, None
        _memo.update(trace=trace, found=found)
        if found:
            line = detail_line(found, seconds)
            if run.get("rehearsal"):
                line = {"rehearsal": True, **line}
            print(json.dumps(line), flush=True)
    return _memo["found"]


def train_ms_of_phase(phase: str) -> Callable:
    """A reader: device ms a training step spends in ``phase``, the
    instruction's own or inherited."""
    def read(registry, trace, run):
        found = train_step_ms(trace, run)
        if not found:
            return None
        return sum(took for (_, its, _), took in found["ms"].items()
                   if its == phase)
    return read
