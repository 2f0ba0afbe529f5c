"""Object bytes ingested in the window over the window's seconds, host
clock."""


def read(run):
    seconds = run.window_s.get("writes", 0.0)
    if "writes" not in run.parts or seconds <= 0:
        return None
    return run.ingested_bytes / 2**20 / seconds
