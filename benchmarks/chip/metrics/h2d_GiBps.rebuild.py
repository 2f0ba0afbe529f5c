"""Bytes put on the device over the seconds the synchronized copies took
(``repro.launch.h2d``)."""


def read(run):
    spans = getattr(run.trace, "spans", None)
    s = (spans or {}).get("repro.launch.h2d")
    if "rebuild" not in run.parts or s is None or s.total_s <= 0:
        return None
    return s.bytes / 2**30 / s.total_s
