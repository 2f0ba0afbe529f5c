"""Median time inside the store per request (``repro.serve.read``, entry
to return of ``read_range``): the read without the client's queueing."""
from chipbench.stats import percentile


def read(run):
    spans = getattr(run.trace, "spans", None)
    s = (spans or {}).get("repro.serve.read")
    if "reads" not in run.parts or s is None:
        return None
    return percentile(s.seconds, 50) * 1e3
