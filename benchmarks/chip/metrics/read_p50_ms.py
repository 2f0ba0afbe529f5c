"""Median of every request of the window, timed from when it was due."""
from chipbench.stats import percentile


def read(run):
    if "reads" not in run.parts:
        return None
    return percentile(run.requests["latency_s"] * 1e3, 50)
