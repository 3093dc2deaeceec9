"""Share of what stands in front of the step's recurrent scans (causal
convolution, SiLU, the split of the fused projection, per-head L2
normalisation) that runs the Pallas kernels (``paddle_tpu/kernels/
causal_conv.py``: forward and backward a kernel each, the projection's
buffer read in place, each part written where the scan's kernels read it):
100 x pallas / all, from the program's ``ssm.conv_calls{path}`` counter
(one increment a traced call, ``path`` = ``pallas`` or ``jnp``).  0 on a
program that has the counter and no kernels; None where the program has no
such counter."""
from benchmarks.lib import scopes


def read(registry, trace, run):
    if run.get("kind") != "train":
        return None
    total = scopes.series_sum(registry, "ssm.conv_calls", lambda l: True)
    if not total:
        return None
    return 100.0 * scopes.series_sum(
        registry, "ssm.conv_calls", lambda l: l["path"] == "pallas") / total
