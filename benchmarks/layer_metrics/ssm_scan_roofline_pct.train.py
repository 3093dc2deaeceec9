"""The scans' share of their roofline in one training step: the least time
the chip could take for every Mamba-2 layer's scan, forward and backward,
by the benchmark's own FLOPs and bytes (``lib/flops_nemotron_h.py``) and
the published peaks, over the device time under the ``ssm_scan`` scope."""
from benchmarks.lib import flops_nemotron_h as shapes
from benchmarks.lib import peaks, scopes


def read(registry, trace, run):
    by_scope = scopes.train_scope_ms(trace, run)
    if not by_scope or not by_scope.get("ssm_scan") or run.get("rehearsal"):
        return None
    model = run["config"]
    least = shapes.scan_least_seconds(
        model["hybrid_override_pattern"].count("M"),
        run["batch"] / run["chips"], run["seq"], model,
        peaks.peaks(run["device_kind"]))
    return 100.0 * least * 1e3 / by_scope["ssm_scan"]
