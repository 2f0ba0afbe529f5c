"""Mean host time of one repair launch: host-to-device copy, dispatch,
kernel, device-to-host copy and sync (compute_seconds over launches)."""


def read(run):
    launches = sum(r["launches"] for r in run.reports)
    if "rebuild" not in run.parts or not launches:
        return None
    return sum(r["compute_seconds"] for r in run.reports) / launches * 1e3
