"""Device time of the feed-forward blocks inside one training step, in ms:
self time of the events under the program's ``mlp`` scope, forward and
backward."""
from benchmarks.lib import scopes

read = scopes.train_ms_of(["mlp"])
