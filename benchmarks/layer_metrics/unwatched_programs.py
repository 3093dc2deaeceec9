"""Programs the process asked the compile cache for outside any watched
entry (``compile.cache{entry="(unwatched)"}``, hits plus misses, process
lifetime): the op-by-op programs of model construction, weight loading
and ``.numpy()``, and the benchmark's own jitted helpers."""
from benchmarks.lib import scopes


def read(registry, trace, run):
    return scopes.series_sum(registry, "compile.cache",
                             lambda l: l["entry"] == scopes.UNWATCHED)
