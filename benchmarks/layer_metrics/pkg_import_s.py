"""Seconds ``import paddle_tpu`` took, top of the package's ``__init__``
to its bottom (``process.import_seconds``; jax's own import is outside it,
``run.py`` imports jax first)."""
from benchmarks.lib import scopes


def read(registry, trace, run):
    return scopes.series_sum(registry, "process.import_seconds",
                             lambda l: True)
