"""The scheduler's ``serving.slot_occupancy`` gauge, sampled by the
benchmark every 50 ms through the window: mean over the engine's slots."""


def read(registry, trace, run):
    if run.get("kind") == "train" or not run.get("slots"):
        return None
    return 100.0 * run["occupancy_mean"] / run["slots"]
