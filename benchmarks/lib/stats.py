"""Percentile and spread arithmetic (copied from
``paddle_tpu/serving/loadgen.py::percentile``; nearest rank)."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile of ``values`` at ``q`` in [0, 1]; raises on
    an empty sample, because a latency nobody measured is not 0."""
    if not values:
        raise ValueError("percentile of an empty sample")
    v = sorted(values)
    idx = max(0, min(len(v) - 1, int(math.ceil(q * len(v))) - 1))
    return float(v[idx])


def median(values: Sequence[float]) -> float:
    return float(statistics.median(values))


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median: the contract's spread (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
