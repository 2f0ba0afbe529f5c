"""Mean host time from a launch's dispatch to its result being ready, with
the inputs already on the device (``repro.launch.device``)."""


def read(run):
    spans = getattr(run.trace, "spans", None)
    s = (spans or {}).get("repro.launch.device")
    if "rebuild" not in run.parts or s is None or not s.count:
        return None
    return s.total_s / s.count * 1e3
