"""Device time by the program's own scopes, and the compile-phase counters.

The program names its work (``paddle_tpu.observability.scopes``: ``attn``,
``mlp``, ``optimizer``, ...) and can say which instruction of a compiled
program carries which name (``scopes.index()``).  The reduced trace
(``lib/trace.py``) holds one event per executed instruction, named by its
HLO text.  This module joins the two:

* :func:`self_times` — the time each event had the device to itself: an
  event that encloses others (a ``while``, an async pair) gives the
  enclosed time away, so the self times of any set of events sum to the
  length of the union of their intervals, never more.
* :func:`scope_ms` — self time summed by scope over the executions of one
  program, per execution, in ms; events whose instruction carries no scope
  are filed under ``unscoped``.
* :func:`series_sum` — the registry snapshot's ``compile.phase_seconds``
  and ``compile.cache`` series.

Everything a reader calls here returns None where there is nothing to
read: no trace, a program that does not publish an index (the parent
commit of the PR that added this), a program name the trace does not hold.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, List, Optional

from benchmarks.lib import trace as trace_mod

UNSCOPED = "unscoped"
UNWATCHED = "(unwatched)"


def instruction_name(text: str) -> str:
    """``%fusion.263 = (...) fusion(...)`` -> ``fusion.263``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def self_times(ops: List[list]) -> List[int]:
    """Self time in ns of each event of ``ops`` (``[text, start, dur]``,
    sorted by start): at every instant the device's time belongs to the
    event that started last among those running."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [0] * len(ops)
    stack: List[int] = []          # running events, innermost last
    cursor = 0

    def close_until(t):
        nonlocal cursor
        while stack:
            top = stack[-1]
            end = ops[top][1] + ops[top][2]
            if end > t:
                break
            if end > cursor:
                own[top] += end - cursor
                cursor = end
            stack.pop()

    for i in order:
        start = ops[i][1]
        close_until(start)
        if stack and start > cursor:
            own[stack[-1]] += start - cursor
        cursor = max(cursor, start)
        stack.append(i)
    close_until(float("inf"))
    return own


def scope_ms(trace: dict, program: str,
             index: Dict[str, Dict[str, Optional[str]]]
             ) -> Optional[Dict[str, float]]:
    """``{scope: ms}`` of one execution of ``jit_<program>`` (the mean over
    the executions the trace holds) on the first device that ran it, with
    ``unscoped`` for events whose instruction has no scope or is not in
    the index.  The values sum to the device's busy time inside the
    program's executions.  None when the trace does not hold the program
    or the index does not know it."""
    table = index.get("jit_" + program)
    if table is None:
        return None
    for dev in trace["devices"].values():
        runs = trace_mod._runs_of(dev, program)
        if not runs:
            continue
        spans = [(s, s + d) for _, s, d in runs]
        ops = [e for e in dev["ops"]
               if any(a <= e[1] < b for a, b in spans)]
        totals: Dict[str, int] = {}
        for event, own in zip(ops, self_times(ops)):
            scope = table.get(instruction_name(event[0])) or UNSCOPED
            totals[scope] = totals.get(scope, 0) + own
        return {k: v * 1e-6 / len(runs) for k, v in totals.items()}
    return None


def program_scope_ms(trace: dict, program: str
                     ) -> Optional[Dict[str, float]]:
    """:func:`scope_ms` with the index the program publishes
    (``paddle_tpu.observability.scopes.index()``: it compiles, a cache
    look-up, so this is for after the window and the check, where the
    readers run).  None where the program has no index to give, or
    anything else goes wrong: a reader must never end a traced run."""
    try:
        from paddle_tpu.observability import scopes
        return scope_ms(trace, program, scopes.index())
    except Exception:
        return None


_memo: dict = {}


def train_scope_ms(trace, run) -> Optional[Dict[str, float]]:
    """:func:`program_scope_ms` of this run's training step, computed once
    for the five readers that share it."""
    if not trace or not trace.get("devices") or run.get("kind") != "train":
        return None
    if _memo.get("trace") is not trace:
        _memo.update(trace=trace, ms=program_scope_ms(trace, "step_fn"))
    return _memo["ms"]


def train_ms_of(scopes: Iterable[str]) -> Callable:
    """A reader: device ms a training step spends under ``scopes``."""
    wanted = tuple(scopes)

    def read(registry, trace, run):
        by_scope = train_scope_ms(trace, run)
        if not by_scope:
            return None
        return sum(by_scope.get(s, 0.0) for s in wanted)
    return read


# -- the registry's compile series ---------------------------------------------

def series_sum(registry, name: str, keep: Callable[[dict], bool]
               ) -> Optional[float]:
    """Sum of the values of ``name``'s series whose labels ``keep``
    accepts; None where the snapshot has no such metric (the parent)."""
    metric = (registry or {}).get(name)
    if not metric:
        return None
    return float(sum(s["value"] for s in metric["series"]
                     if keep(s["labels"])))


def watched_phase_seconds(phase: str) -> Callable:
    """A reader: seconds of one compile phase summed over the watched
    entries (``compile.phase_seconds``, lifetime: set-up is)."""
    def read(registry, trace, run):
        return series_sum(
            registry, "compile.phase_seconds",
            lambda l: l["phase"] == phase and l["entry"] != UNWATCHED)
    return read
