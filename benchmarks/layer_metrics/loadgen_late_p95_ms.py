"""How late the generator ran: actual send minus due time, 95th percentile
over every request of the window, in ms.  A starved generator must not be
read as a fast server."""
from benchmarks.lib import stats


def read(registry, trace, run):
    late = run.get("late_s")
    if not late:
        return None
    return 1e3 * stats.percentile(late, 0.95)
