"""Share of the device's busy time inside the training step that the
program can put under a role, in %: by the instruction's ``own``
``op_name`` (what ``scope_coverage_pct.train`` reads alone), or, for an
instruction without one, by the role its ``user``s or its ``operand``s
agree on (``scopes.instruction_provenance``, the field ``how``).  The rest
is ``unresolved``: the detail line ``step_by_role_and_phase`` names it."""
from benchmarks.lib import provenance


def read(registry, trace, run):
    found = provenance.train_step_ms(trace, run)
    if not found:
        return None
    busy = sum(found["ms"].values())
    if busy <= 0:
        return None
    unresolved = sum(took for (role, _, _), took in found["ms"].items()
                     if role == provenance.UNRESOLVED)
    return 100.0 * (busy - unresolved) / busy
