"""The flash kernels' share of their roofline in one training step: the
least time the chip could take for every layer's causal forward (2 matmuls)
and backward (5), by the benchmark's own FLOPs and bytes and the published
peaks, over the measured ``flash_ms.train``."""
from benchmarks.lib import flops, peaks
from benchmarks.lib import trace as trace_mod


def read(registry, trace, run):
    if trace is None or run.get("kind") != "train" or run.get("rehearsal"):
        return None
    ms = trace_mod.mosaic_ms_per_module(trace, "step_fn")
    if not ms:
        return None
    # a mesh splits the batch over dp and the heads over mp: one chip's share
    least = flops.flash_least_seconds(
        run["config"], run["batch"] / run["chips"], run["seq"],
        peaks.peaks(run["device_kind"]))
    return 100.0 * least["seconds"] * 1e3 / ms
