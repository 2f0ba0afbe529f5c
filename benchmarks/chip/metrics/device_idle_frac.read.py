"""1 - device busy / window, from the trace, mean over the cell's chips."""


def read(run):
    if "reads" not in run.parts or run.trace is None:
        return None
    return 1.0 - run.trace.busy_mean_s / run.trace.window_s
