"""Share of the repairs' wall time that the pipeline hid by overlapping
gather, launch and write-back (overlap_seconds over wall_seconds)."""


def read(run):
    wall = sum(r["wall_seconds"] for r in run.reports)
    if "rebuild" not in run.parts or wall <= 0:
        return None
    return sum(r["overlap_seconds"] for r in run.reports) / wall
