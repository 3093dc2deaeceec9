"""A stand-in for ``harness.Run`` for tests that build a model through the
benchmark's own path.  (Its own module: ``conftest`` is not a name to import,
``tests/`` has one too.)"""
import types


class QuietRun:
    """What the runners need of a ``harness.Run``, printing nothing."""

    def __init__(self, seed):
        self.args = types.SimpleNamespace(seed=seed, trace=0, seconds=1.0)
        self.record = {"chips": 1, "rehearsal": True}

    def part(self, name):
        pass

    def emit(self, **fields):
        pass
