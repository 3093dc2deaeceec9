"""Share of the traced window in which no operation ran on the device."""
from benchmarks.lib import trace as trace_mod


def read(registry, trace, run):
    if trace is None or not trace["devices"] or run.get("kind") != "train":
        return None
    return trace_mod.idle_share_pct(trace)
