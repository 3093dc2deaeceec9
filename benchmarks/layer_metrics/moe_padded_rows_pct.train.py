"""Share of the rows the grouped products are launched over that uniform
routing leaves empty: 100 x (launched - expected_held) / launched, from the
program's ``moe.rows{which}`` counter (trace-time, one increment an expert
layer traced).  None where the program has no such counter."""
from benchmarks.lib import scopes


def read(registry, trace, run):
    if run.get("kind") != "train":
        return None
    rows = {which: scopes.series_sum(registry, "moe.rows",
                                     lambda l, w=which: l["which"] == w)
            for which in ("launched", "expected_held")}
    if not rows["launched"]:
        return None
    return 100.0 * (rows["launched"] - rows["expected_held"]) \
        / rows["launched"]
