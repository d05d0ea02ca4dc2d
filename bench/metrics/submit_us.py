"""Front door: median host time inside ``submit_async``, in us, from
the benchmark's own clock around each call in the window."""
import statistics


def read(ctx):
    xs = ctx.served.submit_s
    return statistics.median(xs) * 1e6 if xs else None
