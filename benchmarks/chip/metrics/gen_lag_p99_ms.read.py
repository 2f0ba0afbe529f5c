"""99th percentile of how late the load generator sent each request past
its due time: a starved generator, not a slow server."""
from chipbench.stats import percentile


def read(run):
    if "reads" not in run.parts:
        return None
    return percentile(run.requests["lag_s"] * 1e3, 99)
