"""95th percentile of the scheduler's ``serving.queue_wait_seconds``
histogram (submission to admission into a slot), in ms.  The histogram is the
program's: log-spaced buckets (about 21% wide), process lifetime, so it holds
the six warm-up requests beside the window's."""


def read(registry, trace, run):
    series = registry.get("serving.queue_wait_seconds", {}).get("series")
    if not series or not series[0].get("count"):
        return None
    return 1e3 * series[0]["p95"]
