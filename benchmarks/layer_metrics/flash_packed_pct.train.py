"""Share of the step's flash forward calls that read q, k and v where the
fused projection wrote them (three block index maps onto the (b, s,
3*h*d) buffer; no slice pass in front of the kernel): 100 x packed / all,
from the program's ``flash.fwd_calls{operands}`` counter (one increment a
traced forward call, ``operands`` = ``packed`` or ``split``).  None where
the program has no such counter (the parent of the PR that added it)."""
from benchmarks.lib import scopes


def read(registry, trace, run):
    if run.get("kind") != "train":
        return None
    total = scopes.series_sum(registry, "flash.fwd_calls", lambda l: True)
    if not total:
        return None
    return 100.0 * scopes.series_sum(
        registry, "flash.fwd_calls",
        lambda l: l["operands"] == "packed") / total
