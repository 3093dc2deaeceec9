"""The grouped products' share of their roofline in one training step: the
least time the chip could take for the six products of every expert layer
over the rows uniform routing sends to the held experts, weights read once
a product (``lib/flops_nemotron_h.py``, the published peaks), over the
device time under the ``moe_experts`` scope."""
from benchmarks.lib import flops_nemotron_h as shapes
from benchmarks.lib import peaks, scopes


def read(registry, trace, run):
    by_scope = scopes.train_scope_ms(trace, run)
    if (not by_scope or not by_scope.get("moe_experts")
            or run.get("rehearsal")):
        return None
    model = run["config"]
    least = shapes.grouped_least_seconds(
        model["hybrid_override_pattern"].count("E"),
        run["batch"] / run["chips"], run["seq"], model,
        peaks.peaks(run["device_kind"]))
    return 100.0 * least * 1e3 / by_scope["moe_experts"]
