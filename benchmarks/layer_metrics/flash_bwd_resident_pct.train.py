"""Share of the step's differentiated flash calls that ran the resident
backward (one grid cell a (batch, head group); takes O, forms delta
itself): 100 x resident / all, from the program's
``flash.bwd_calls{path}`` counter (one increment a traced backward call,
``path`` the residency its shape chose).  None where the program has no
such counter (the parent of the PR that added it)."""
from benchmarks.lib import scopes


def read(registry, trace, run):
    if run.get("kind") != "train":
        return None
    total = scopes.series_sum(registry, "flash.bwd_calls", lambda l: True)
    if not total:
        return None
    return 100.0 * scopes.series_sum(
        registry, "flash.bwd_calls",
        lambda l: l["path"] == "resident") / total
