"""Seconds JAX spent lowering the watched entries' jaxprs to StableHLO
(``compile.phase_seconds{phase="lower"}``, summed over the watched
entries, process lifetime)."""
from benchmarks.lib import scopes

read = scopes.watched_phase_seconds("lower")
