"""Device time of the Mosaic (Pallas) calls a training step makes under one
of the program's scopes.

``lib/trace.py::mosaic_ms_per_module`` sums every Mosaic call of the step,
which is the flash kernels' time only while they are the step's only
kernels.  A step with other kernels (a grouped product, a scan) needs the
program's word on which call is whose: the instruction's scope
(``paddle_tpu.observability.scopes.index()``, as ``lib/scopes.py`` joins it
to the trace).  None where there is no trace, no index (a program older
than the scopes) or no such call.
"""
from __future__ import annotations

from typing import Optional

from benchmarks.lib import scopes
from benchmarks.lib import trace as trace_mod


def mosaic_ms_under(trace, run, scope: str) -> Optional[float]:
    """Summed duration, in ms an execution, of the Mosaic calls of
    ``jit_step_fn`` whose instruction carries ``scope``."""
    if not trace or not trace.get("devices") or run.get("kind") != "train":
        return None
    try:
        from paddle_tpu.observability import scopes as program_scopes
        table = program_scopes.index().get("jit_step_fn")
    except Exception:
        return None
    if not table:
        return None
    for dev in trace["devices"].values():
        runs = trace_mod._runs_of(dev, "step_fn")
        if not runs:
            continue
        spans = [(s, s + d) for _, s, d in runs]
        total = sum(
            dur for text, start, dur in dev["ops"]
            if trace_mod.MOSAIC_MARK in text
            and table.get(scopes.instruction_name(text)) == scope
            and any(a <= start < b for a, b in spans))
        return total * 1e-6 / len(runs) or None
    return None
