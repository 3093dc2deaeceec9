"""Summed device time of the flash kernels inside one training step, in ms,
for a step that has other kernels too: the Mosaic calls whose instruction
carries the program's ``attn`` scope, forward and backward
(``flash_ms.train`` sums every Mosaic call of the step).  None where the
program publishes no scopes."""
from benchmarks.lib import scoped_kernels


def read(registry, trace, run):
    return scoped_kernels.mosaic_ms_under(trace, run, "attn")
