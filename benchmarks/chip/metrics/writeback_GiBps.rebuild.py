"""Rebuilt bytes written back over the seconds the write-backs took
(``repro.repair.writeback``, on the writer thread)."""


def read(run):
    spans = getattr(run.trace, "spans", None)
    s = (spans or {}).get("repro.repair.writeback")
    if "rebuild" not in run.parts or s is None or s.total_s <= 0:
        return None
    return s.bytes / 2**30 / s.total_s
