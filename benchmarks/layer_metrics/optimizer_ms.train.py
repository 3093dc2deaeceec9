"""Device time of the optimizer's update inside one training step, in ms:
self time of the events whose instruction carries the program's
``optimizer`` scope (``TrainStep`` opens it around ``apply_gradients``)."""
from benchmarks.lib import scopes

read = scopes.train_ms_of(["optimizer"])
