"""Share of the window's repair windows whose gather landed in one of the
store's two kept buffers with nothing allocated: the repair reports'
gather_buffer_reuses over reuses + gather_buffer_allocs. A program whose
reports lack the counters reads nothing."""


def read(run):
    reports = run.reports
    if ("rebuild" not in run.parts or not reports
            or any("gather_buffer_reuses" not in r for r in reports)):
        return None
    reuses = sum(r["gather_buffer_reuses"] for r in reports)
    windows = reuses + sum(r["gather_buffer_allocs"] for r in reports)
    return reuses / windows if windows else None
