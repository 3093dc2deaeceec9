"""Device time of the expert layers inside one training step, in ms: self
time of the events under the program's ``moe`` (router, top-k, sort,
gather, scatter, shared expert) and ``moe_experts`` (the grouped products
alone) scopes, forward and backward."""
from benchmarks.lib import scopes

read = scopes.train_ms_of(["moe", "moe_experts"])
