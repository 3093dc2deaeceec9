"""Share of the step's Mamba-2 scans that run the Pallas kernels
(``paddle_tpu/kernels/ssd_scan.py``: forward and backward a kernel each,
the (chunk, chunk) decays in VMEM alone): 100 x pallas / all, from the
program's ``ssm.scan_calls{path}`` counter (one increment a traced scan,
``path`` = ``pallas`` or ``chunked_jnp``).  0 on a program that has the
counter and no kernels; None where the program has no such counter."""
from benchmarks.lib import scopes


def read(registry, trace, run):
    if run.get("kind") != "train":
        return None
    total = scopes.series_sum(registry, "ssm.scan_calls", lambda l: True)
    if not total:
        return None
    return 100.0 * scopes.series_sum(
        registry, "ssm.scan_calls", lambda l: l["path"] == "pallas") / total
