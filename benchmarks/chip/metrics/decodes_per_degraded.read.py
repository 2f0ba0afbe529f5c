"""Decode launches per degraded read over the window, from the store's
counters: below 1 where the hot-block cache and coalescing serve reads."""


def read(run):
    c = run.counters
    if "reads" not in run.parts or not c.get("degraded_reads"):
        return None
    return c["serve_decode_launches"] / c["degraded_reads"]
