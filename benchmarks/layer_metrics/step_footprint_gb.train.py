"""What the device holds while the compiled train step runs, by the step's
own memory analysis: arguments + outputs - aliased (the donated state comes
back in place) + temporaries + generated code (``harness.program_bytes``,
read after the window from the executable the window drove).
``hbm_peak_gb.train`` beside it counts live buffers only and never sees
the temporaries: the activations kept for the backward."""


def read(registry, trace, run):
    held = run.get("step_program_bytes")
    if run.get("kind") != "train" or run.get("rehearsal") or not held:
        return None
    return held["total"] / 1e9
