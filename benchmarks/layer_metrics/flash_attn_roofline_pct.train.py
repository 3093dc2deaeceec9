"""The flash kernels' share of their roofline in one training step, for a
step that has other kernels too: the least time of the family's
``flash_calls`` (``lib/flops.py``, the published peaks) over
``flash_attn_ms.train``, the Mosaic calls under the ``attn`` scope.  A
key/value group expanded in front of the kernels is counted as the kernels
see it: all the query heads."""
from benchmarks.lib import flops, peaks, scoped_kernels


def read(registry, trace, run):
    if run.get("rehearsal"):
        return None
    ms = scoped_kernels.mosaic_ms_under(trace, run, "attn")
    calls = run["family"].flash_calls(run["config"]) if ms else None
    if not calls:
        return None
    least = flops.flash_least_seconds(
        calls, run["batch"] / run["chips"], run["seq"],
        peaks.peaks(run["device_kind"]))
    return 100.0 * least["seconds"] * 1e3 / ms
