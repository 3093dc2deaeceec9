"""Device time one training step spends in passes over memory that compute
nothing, in ms, whatever their role: self time of the events whose
instruction the program marks ``moves_only`` (``scopes.MOVES_ONLY_OPCODES``:
a copy, transpose, reshape, convert, slice, concatenate, pad or broadcast,
an async pair of one, or a fusion of these alone)."""
from benchmarks.lib import provenance


def read(registry, trace, run):
    found = provenance.train_step_ms(trace, run)
    if not found:
        return None
    return sum(found["moves_only_ms"].values())
