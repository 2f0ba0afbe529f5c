"""Share of the HBM roofline of the window's GF(2^8) products.

The least device traffic of the repairs is every gathered byte read once
and every rebuilt byte written once; at the chip's peak HBM bandwidth,
spread over the cell's chips, that takes the least time. The share is that
time over the device busy time of the traced window, so packing passes and
any other device work count as cost. A GF product does at most |reads|
multiply-adds per byte moved and the chip has no published GF op peak, so
the bound is bandwidth.
"""


def read(run):
    if "rebuild" not in run.parts or run.trace is None:
        return None
    busy = run.trace.busy_mean_s
    if busy <= 0:
        return None
    moved = sum(r["bytes_read"] for r in run.reports) + run.rebuilt_bytes
    least = moved / (run.chips * run.peaks.hbm_bytes_per_s)
    return 100.0 * least / busy
