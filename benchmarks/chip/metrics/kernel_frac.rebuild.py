"""Share of the device busy time spent in the GF kernels themselves, found
by their stable name (``%gf_kernel_``): the rest is packing, copies and
converts around them."""


def read(run):
    kernel_s = getattr(run.trace, "kernel_s", None)
    if "rebuild" not in run.parts or kernel_s is None:
        return None
    busy = run.trace.busy_mean_s
    if busy <= 0:
        return None
    return 100.0 * kernel_s / busy
