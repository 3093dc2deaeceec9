"""Share of the score elements the causal flash kernels compute that the
mask sets to zero: 100 x (computed - causal) / computed, from the
program's ``flash.score_elements{which}`` counters (written at trace time
from the kernels' own block and sub-tile walk).  None where the program
has no such counter (the parent of the PR that added it)."""
from benchmarks.lib import scopes


def read(registry, trace, run):
    if run.get("kind") != "train":
        return None
    computed, causal = (
        scopes.series_sum(registry, "flash.score_elements",
                          lambda l, which=which: l["which"] == which)
        for which in ("computed", "causal"))
    if not computed:
        return None
    return 100.0 * (computed - causal) / computed
