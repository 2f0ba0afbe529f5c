"""Bytes the window's repairs gathered over the seconds their gathers took
(the repair reports' bytes_read and read_seconds)."""


def read(run):
    seconds = sum(r["read_seconds"] for r in run.reports)
    if "rebuild" not in run.parts or seconds <= 0:
        return None
    return sum(r["bytes_read"] for r in run.reports) / 2**30 / seconds
