"""Kernels: device time of the histogram route's label gather per
launch, in ms, from the profiler trace (averaged over the chips the cell
uses)."""


def read(ctx):
    s, n = ctx.kernel_seconds("gather"), ctx.delta("batches")
    if s <= 0 or n <= 0:
        return None
    return 1e3 * s / ctx.trace["n_devices"] / n
