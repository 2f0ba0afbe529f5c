"""Mean coordinator time per repair launch putting a one-chip window's stack
on the device before the pipeline's launch thread runs the launch (the
repair reports' stack_put_seconds over launches). A program whose reports
lack stack_put_seconds reads nothing."""


def read(run):
    reports = run.reports
    launches = sum(r["launches"] for r in reports)
    if ("rebuild" not in run.parts or not launches
            or any("stack_put_seconds" not in r for r in reports)):
        return None
    return sum(r["stack_put_seconds"] for r in reports) / launches * 1e3
