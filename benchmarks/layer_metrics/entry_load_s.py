"""Seconds the backend took to hand the watched entries their executables
(``compile.phase_seconds{phase="backend"}``, summed over the watched
entries, process lifetime): with a warm compile cache the read and
deserialisation, with a cold one XLA's compile."""
from benchmarks.lib import scopes

read = scopes.watched_phase_seconds("backend")
