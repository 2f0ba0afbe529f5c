"""All bytes rebuilt in the window over the window's seconds, host clock."""


def read(run):
    seconds = run.window_s.get("rebuild", 0.0)
    if "rebuild" not in run.parts or seconds <= 0:
        return None
    return run.rebuilt_bytes / 2**20 / seconds
