"""Device time of the backward pass inside one training step, in ms: self
time of the events whose instruction the program files under the phase
``backward`` (a ``transpose(`` wrapper in its ``op_name``: ``custom_vjp``
backward rules and the backward of a checkpointed block included; or
inherited from the work it serves: ``scopes.instruction_provenance``)."""
from benchmarks.lib import provenance

read = provenance.train_ms_of_phase("backward")
