"""The scheduler's ``host_gap_seconds`` (wall time with no decode step
dispatched and unconsumed: the only time the host can starve the device)
accrued in the window, over the decode steps of the window, in ms a step."""


def read(registry, trace, run):
    if run.get("kind") == "train" or not run.get("decode_steps"):
        return None
    return 1e3 * run["host_gap_s"] / run["decode_steps"]
