"""Device time of the linear-attention (Gated DeltaNet) layers inside one
training step, in ms: self time of the events under the program's
``linear_attn`` (projections, convolution, normalisations, gates, gated
norm) and ``linear_attn_scan`` (the delta rule alone) scopes, forward and
backward."""
from benchmarks.lib import scopes

read = scopes.train_ms_of(["linear_attn", "linear_attn_scan"])
