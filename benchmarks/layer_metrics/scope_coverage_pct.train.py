"""Share of the device's busy time inside the training step that falls to
events carrying one of the program's scopes, in %: what the by-scope
metrics can see.  The rest is ``unscoped``: residual adds, the casts of
the f32 masters, copies XLA placed between the blocks."""
from benchmarks.lib import scopes


def read(registry, trace, run):
    by_scope = scopes.train_scope_ms(trace, run)
    if not by_scope:
        return None
    busy = sum(by_scope.values())
    if busy <= 0:
        return None
    return 100.0 * (busy - by_scope.get(scopes.UNSCOPED, 0.0)) / busy
