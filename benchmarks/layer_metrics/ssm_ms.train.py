"""Device time of the state-space layers inside one training step, in ms:
self time of the events under the program's ``ssm`` (projections,
convolution, gated norm) and ``ssm_scan`` (the scan alone) scopes, forward
and backward."""
from benchmarks.lib import scopes

read = scopes.train_ms_of(["ssm", "ssm_scan"])
