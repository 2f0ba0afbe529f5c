"""Median of the requests for lost blocks alone, each timed from when it
was due: the wait of a reader who hits a lost block."""
from chipbench.stats import percentile


def read(run):
    if "reads" not in run.parts:
        return None
    r = run.requests
    return percentile(r["latency_s"][r["degraded"]] * 1e3, 50)
