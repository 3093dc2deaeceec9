"""Median device duration of the compiled training step (the ``jit_step_fn``
module event of the trace), in ms."""
from benchmarks.lib import trace as trace_mod


def read(registry, trace, run):
    if trace is None or run.get("kind") != "train":
        return None
    return trace_mod.module_median_ms(trace, "step_fn")
