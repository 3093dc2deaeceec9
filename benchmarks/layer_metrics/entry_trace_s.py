"""Seconds JAX spent tracing the watched entries' Python into jaxprs
(``compile.phase_seconds{phase="trace"}``, summed over the watched
entries, process lifetime): the part of set-up that no compile cache
shortens."""
from benchmarks.lib import scopes

read = scopes.watched_phase_seconds("trace")
