"""The grouped products' share of their roofline in one training step of a
model with GATED experts: the least time the chip could take for the nine
products of every expert layer (gate, up and down, each once forward and
twice backward) over the rows uniform routing sends to the held experts,
weights read once a product (``lib/flops_qwen3_next.py``, the published
peaks), over the device time under the ``moe_experts`` scope.
(``moe_experts_roofline_pct.train`` counts the six products of ungated
experts from ``nemotron_h``'s pattern string.)"""
from benchmarks.lib import flops_qwen3_next as shapes
from benchmarks.lib import peaks, scopes


def read(registry, trace, run):
    by_scope = scopes.train_scope_ms(trace, run)
    if (not by_scope or not by_scope.get("moe_experts")
            or run.get("rehearsal")):
        return None
    model = run["config"]
    least = shapes.gated_grouped_least_seconds(
        model["num_hidden_layers"], run["batch"] / run["chips"], run["seq"],
        model, peaks.peaks(run["device_kind"]))
    return 100.0 * least * 1e3 / by_scope["moe_experts"]
