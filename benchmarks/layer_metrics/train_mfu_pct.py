"""Model-FLOP utilisation: the benchmark's own FLOPs a token (forward and
backward, causal attention, no recompute) times the window's tokens a second,
over chips times the published peak."""
from benchmarks.lib import flops, peaks


def read(registry, trace, run):
    if run.get("kind") != "train" or run.get("rehearsal"):
        return None
    per_token = flops.model_flops_per_token(run["config"], run["seq"])
    peak = peaks.peaks(run["device_kind"])["flops"]
    rate = run["end_to_end"]["train_tokens_per_s"]
    return 100.0 * per_token * rate / (run["chips"] * peak)
