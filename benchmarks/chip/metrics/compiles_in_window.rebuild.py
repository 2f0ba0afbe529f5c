"""XLA programs built or loaded inside the window (compile clock); the
warm-up should leave none."""


def read(run):
    if "rebuild" not in run.parts:
        return None
    return run.compiles_in_window
