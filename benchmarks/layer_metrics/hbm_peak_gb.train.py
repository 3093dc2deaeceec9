"""``peak_bytes_in_use`` of the fullest chip, read right after the window
and before the check.  A process-lifetime peak: it includes set-up."""


def read(registry, trace, run):
    if run.get("kind") != "train" or run.get("rehearsal"):
        return None
    return run["device"]["memory_peak_bytes"] / 1e9
