"""Device time of attention inside one training step, in ms: self time of
the events under the program's ``attn`` scope, forward and backward: the
flash kernels (``flash_ms.train`` is inside it) and the qkv and output
projections with what XLA fused onto them."""
from benchmarks.lib import scopes

read = scopes.train_ms_of(["attn"])
