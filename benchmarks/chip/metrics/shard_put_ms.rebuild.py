"""Mean coordinator time per repair launch putting a sharded launch's
per-shard buffers on their chips (the repair reports' put_seconds over
launches). A program whose reports lack put_seconds reads nothing."""


def read(run):
    reports = run.reports
    launches = sum(r["launches"] for r in reports)
    if ("rebuild" not in run.parts or not launches
            or any("put_seconds" not in r for r in reports)):
        return None
    return sum(r["put_seconds"] for r in reports) / launches * 1e3
