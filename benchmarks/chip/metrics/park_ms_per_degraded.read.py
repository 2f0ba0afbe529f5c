"""Time degraded readers spent parked on another reader's in-flight decode
(``repro.serve.park``), over the degraded reads (``repro.serve.read`` with
``degraded`` set)."""


def read(run):
    spans = getattr(run.trace, "spans", None)
    reads = (spans or {}).get("repro.serve.read")
    if "reads" not in run.parts or reads is None or not reads.degraded:
        return None
    park = spans.get("repro.serve.park")
    return (park.total_s if park else 0.0) / reads.degraded * 1e3
