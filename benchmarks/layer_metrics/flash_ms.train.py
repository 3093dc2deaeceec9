"""Summed device time of the Mosaic calls inside one training step, in ms:
events whose own HLO text holds ``custom_call_target="tpu_custom_call"``.
With every Pallas opt-in flag off these are the flash forward and backward
kernels, two a layer."""
from benchmarks.lib import trace as trace_mod


def read(registry, trace, run):
    if trace is None or run.get("kind") != "train":
        return None
    ms = trace_mod.mosaic_ms_per_module(trace, "step_fn")
    return ms if ms else None
