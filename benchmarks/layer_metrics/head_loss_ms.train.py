"""Device time of the model's two ends inside one training step, in ms:
self time of the events under the program's ``embed``, ``lm_head`` and
``loss`` scopes (the embeddings and their scatter-add backward, the tied
head's two GEMMs, cross entropy)."""
from benchmarks.lib import scopes

read = scopes.train_ms_of(["embed", "lm_head", "loss"])
