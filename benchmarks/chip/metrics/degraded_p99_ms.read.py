"""99th percentile of the requests for lost blocks alone, from due time."""
from chipbench.stats import percentile


def read(run):
    if "reads" not in run.parts:
        return None
    r = run.requests
    return percentile(r["latency_s"][r["degraded"]] * 1e3, 99)
