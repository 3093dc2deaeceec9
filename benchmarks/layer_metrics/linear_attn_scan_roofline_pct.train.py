"""The delta rules' share of their roofline in one training step: the least
time the chip could take for every Gated DeltaNet layer's delta rule,
forward and backward, by the benchmark's own FLOPs and bytes
(``lib/flops_qwen3_next.py``) and the published peaks, over the device time
under the ``linear_attn_scan`` scope."""
from benchmarks.lib import flops_qwen3_next as shapes
from benchmarks.lib import peaks, scopes
from benchmarks.reference import qwen3_next_ref as ref


def read(registry, trace, run):
    by_scope = scopes.train_scope_ms(trace, run)
    if (not by_scope or not by_scope.get("linear_attn_scan")
            or run.get("rehearsal")):
        return None
    model = run["config"]
    least = shapes.delta_rule_least_seconds(
        ref.layer_types(model).count(ref.LINEAR),
        run["batch"] / run["chips"], run["seq"], model,
        peaks.peaks(run["device_kind"]))
    return 100.0 * least * 1e3 / by_scope["linear_attn_scan"]
