"""Share of the step's gated delta rules that run the Pallas kernels
(``paddle_tpu/kernels/delta_rule.py``: forward and backward a kernel each,
a chunk's (C, C) system made, inverted and used in VMEM alone): 100 x
pallas / all, from the program's ``linear_attn.scan_calls{path}`` counter
(one increment a traced rule, ``path`` = ``pallas`` or ``chunked_jnp``).  0
on a program that has the counter and no kernels; None where the program
has no such counter."""
from benchmarks.lib import scopes


def read(registry, trace, run):
    if run.get("kind") != "train":
        return None
    total = scopes.series_sum(registry, "linear_attn.scan_calls",
                              lambda l: True)
    if not total:
        return None
    return 100.0 * scopes.series_sum(
        registry, "linear_attn.scan_calls",
        lambda l: l["path"] == "pallas") / total
