"""Device time one training step spends running a forward a second time,
in ms: self time of the events whose instruction the program files under
the phase ``recompute`` (``rematted_computation`` in its ``op_name``, what
``jax.checkpoint`` names the forward it runs again inside the backward; or
inherited from the work it serves).  0 on a step that checkpoints
nothing."""
from benchmarks.lib import provenance

read = provenance.train_ms_of_phase("recompute")
