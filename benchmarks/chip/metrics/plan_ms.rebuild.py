"""Mean host time of a repair's planning (``repro.repair.plan``): the scan
for lost blocks, destinations, compiled plans and the window schedule, up
to the first window's prefetch."""


def read(run):
    spans = getattr(run.trace, "spans", None)
    s = (spans or {}).get("repro.repair.plan")
    if "rebuild" not in run.parts or s is None or not s.count:
        return None
    return s.total_s / s.count * 1e3
