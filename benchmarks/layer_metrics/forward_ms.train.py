"""Device time of the forward pass inside one training step, in ms: self
time of the events whose instruction the program files under the phase
``forward`` (``paddle_tpu.observability.scopes.phase_of`` on its own
``op_name``, or inherited from the work it serves:
``scopes.instruction_provenance``).  The forward a ``jax.checkpoint`` runs
again is ``recompute_ms.train``'s, not this."""
from benchmarks.lib import provenance

read = provenance.train_ms_of_phase("forward")
