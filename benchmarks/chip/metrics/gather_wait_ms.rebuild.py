"""Time the repair coordinator spent blocked on a window's reads
(``repro.repair.gather_wait``) per launch: the gather the pipeline did not
hide."""


def read(run):
    spans = getattr(run.trace, "spans", None)
    s = (spans or {}).get("repro.repair.gather_wait")
    launches = sum(r["launches"] for r in run.reports)
    if "rebuild" not in run.parts or s is None or not launches:
        return None
    return s.total_s / launches * 1e3
