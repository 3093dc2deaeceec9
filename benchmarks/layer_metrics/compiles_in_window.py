"""Programs compiled by the watched entries inside the window:
``obs.compile_counts()`` after the window minus before.  Should be 0."""


def read(registry, trace, run):
    before, after = run["compiles_before"], run["compiles_after"]
    return float(sum(after.get(k, 0) - before.get(k, 0) for k in after))
