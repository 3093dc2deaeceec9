"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s in bf16, 16 GB of HBM2e at 819 GB/s per chip.  Copied from
``paddle_tpu/observability/costs.py`` (PEAK_*_BY_KIND) so that a later change
to the program cannot move the yardstick.  A part that is not in the table is
an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; raises on an unknown part."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no published peak for device kind %r: add it to "
                       "benchmarks/lib/peaks.py with its source"
                       % (device_kind,)) from None
